"""Parity between the batched vmapped round engine and the reference
per-client loop engine (ISSUE 1 acceptance): identical selection masks
and matching accuracy trajectories for all three schemes, plus
straggler masking via zeroed FedAvg weights.

ISSUE 2 extends the same harness to the capacity-grouped engine: the
standard profile below already yields two capacity groups (120- and
40-sample quantities), a dedicated test drives a Table-3-shaped skew,
and empty rounds (nobody clears selection + deadline) must be a no-op
broadcast in both engines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fl.aggregation import fedavg, fedavg_masked
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig

N_CLIENTS = 10
N_ROUNDS = 3


def _cfg(scheme: str, **kw) -> FLSimConfig:
    kw.setdefault("partition",
                  PartitionConfig(n_clients=N_CLIENTS, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9))
    kw.setdefault("mobility", MobilityConfig(n_vehicles=N_CLIENTS, seed=0))
    return FLSimConfig(
        scheme=scheme, n_rounds=N_ROUNDS, local_epochs=1,
        samples_per_class=260, probe_samples=64, seed=0, **kw)


def _sim(scheme: str, engine: str, **kw) -> FLSimulation:
    return FLSimulation(_cfg(scheme, **kw), run=RunConfig(engine=engine))


def _run(scheme: str, engine: str, **kw):
    sim = _sim(scheme, engine, **kw)
    rows, masks = [], []
    for r in range(N_ROUNDS):
        rows.append(sim.run_round(r))
        masks.append(sim.last_mask.copy())
    return rows, masks


@pytest.mark.parametrize("scheme", ["dcs", "ccs-fuzzy", "random"])
def test_engine_parity(scheme):
    rows_l, masks_l = _run(scheme, "loop")
    rows_b, masks_b = _run(scheme, "batched")
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(
            masks_l[r], masks_b[r],
            err_msg=f"{scheme} round {r}: selection masks diverge")
        assert rows_l[r]["n_selected"] == rows_b[r]["n_selected"]
        assert rows_l[r]["n_aggregated"] == rows_b[r]["n_aggregated"]
        assert rows_l[r]["n_straggler"] == rows_b[r]["n_straggler"]
        assert abs(rows_l[r]["accuracy"] - rows_b[r]["accuracy"]) <= 1e-5, \
            f"{scheme} round {r}: accuracy diverges"


def test_engine_rejects_unknown():
    with pytest.raises(ValueError):
        FLSimulation(_cfg("dcs"), run=RunConfig(engine="other"))


def test_dataset_loss_batch_matches_per_client():
    """The stacked-cohort probe API agrees with per-client dataset_loss,
    including when C*cap is not a multiple of the chunk size."""
    from repro.configs.mnist_cnn import CONFIG as CNN_CFG
    from repro.fl.client import dataset_loss, dataset_loss_batch
    from repro.models.cnn import init_cnn

    params = init_cnn(jax.random.PRNGKey(0), CNN_CFG)
    im = jax.random.normal(jax.random.PRNGKey(1), (5, 60, 28, 28, 1))
    lb = jnp.zeros((5, 60), jnp.int32).at[:, :40].set(2)
    nv = jnp.arange(10, 60, 10, dtype=jnp.int32)        # ragged validity
    got = np.asarray(dataset_loss_batch(params, im, lb, nv, batch=128))
    want = np.array([float(dataset_loss(params, im[i], lb[i], nv[i],
                                        batch=128)) for i in range(5)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# straggler masking
# --------------------------------------------------------------------------

def test_fedavg_masked_zero_weight_rows_drop_out():
    """A zero FedAvg weight is exactly equivalent to skipping the model."""
    rows = jnp.arange(12.0).reshape(3, 4)
    stacked = {"w": rows}
    out = fedavg_masked(stacked, jnp.array([2.0, 0.0, 1.0]))
    ref = fedavg([{"w": rows[0]}, {"w": rows[2]}], [2.0, 1.0])
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(ref["w"]),
                               rtol=1e-6)


def test_grouped_parity_table3_skew():
    """Grouped-engine parity on a Table-3-shaped quantity skew (200 vs 45
    samples -> two capacity groups): masks identical to the loop engine,
    accuracy within 1e-5."""
    kw = dict(partition=PartitionConfig(n_clients=N_CLIENTS, big_clients=3,
                                        big_quantity=200, small_quantity=45,
                                        classes_per_client=9))
    rows_l, masks_l = _run("dcs", "loop", **kw)
    rows_b, masks_b = _run("dcs", "batched", **kw)
    sim = _sim("dcs", "batched", **kw)
    assert [g.cap for g in sim.groups] == [200, 60]
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(masks_l[r], masks_b[r])
        assert rows_l[r]["n_aggregated"] == rows_b[r]["n_aggregated"]
        assert abs(rows_l[r]["accuracy"] - rows_b[r]["accuracy"]) <= 1e-5


def test_uniform_capacity_single_group():
    """uniform_capacity=True reproduces the PR-1 single max-cap stack."""
    sim = _sim("dcs", "batched", uniform_capacity=True)
    assert len(sim.groups) == 1
    assert sim.groups[0].cap == sim.cap
    assert sim.groups[0].size == N_CLIENTS


def test_partial_group_cohort_parity():
    """A cohort confined to one capacity group trains identically in both
    engines (the batched engine must skip the other group's empty cohort
    rather than pad from it)."""
    sim_b = _sim("dcs", "batched")
    sim_l = _sim("dcs", "loop")
    survivors = np.zeros(N_CLIENTS, bool)
    survivors[[4, 7]] = True                 # small-capacity clients only
    sim_b.params = sim_b._train_batched(survivors, sim_b._round_keys(0))
    sim_l.params = sim_l._train_loop(survivors, sim_l._round_keys(0))
    for a, b in zip(jax.tree.leaves(sim_b.params),
                    jax.tree.leaves(sim_l.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-6)


# --------------------------------------------------------------------------
# empty rounds
# --------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["loop", "batched"])
def test_empty_round_is_noop_broadcast(engine):
    """When every evaluation is below E_tau nobody is selected: the round
    must leave the global model bit-identical in both engines."""
    sim = _sim("dcs", engine, e_tau=1e9)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(sim.params)]
    row = sim.run_round(0)
    assert row["n_selected"] == 0
    assert row["n_aggregated"] == 0
    assert row["mean_eval_selected"] == 0.0
    for b, a in zip(before, jax.tree.leaves(sim.params)):
        np.testing.assert_array_equal(b, np.asarray(a))


def test_all_stragglers_leave_global_model_untouched():
    """With an unmeetable deadline every selected client straggles: the
    batched engine must aggregate nothing and keep the exact params."""
    sim = _sim("ccs-fuzzy", "batched", deadline_s=1e-9)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(sim.params)]
    row = sim.run_round(0)
    assert row["n_selected"] > 0
    assert row["n_aggregated"] == 0
    assert row["n_straggler"] == row["n_selected"]
    after = jax.tree.leaves(sim.params)
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, np.asarray(a))
