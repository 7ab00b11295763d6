"""Event-driven streaming fleet (ISSUE 6): sync bit-parity, churn and
staleness edge cases, the scheme registry, and the RunConfig surface.

Parity pins (acceptance): with churn disabled, staleness "drop" and the
cadence at the round period, the event-driven server reproduces the
serial driver's rows AND final params **bit-identically** — on a single
device, through the sweep's seed-vmapped dispatch, and on a forced
4-device clients mesh (subprocess, like tests/test_sharding.py).

Edge cases (ISSUE 6 satellites): an all-departed round is a no-op
broadcast; when every survivor straggles, aggregation waits for a later
cadence tick; a client departing coverage mid-training loses its pending
update; ``staleness_weight`` is property-tested for monotonicity.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.fl import schemes
from repro.fl.async_server import EventDrivenServer
from repro.fl.mobility import MobilityConfig, coverage_active
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.fl.schemes import get_scheme, register_scheme, scheme_names
from repro.fl.timing import staleness_weight

REPO = Path(__file__).resolve().parent.parent

N_CLIENTS = 10
N_ROUNDS = 3


def _cfg(scheme: str = "ccs-fuzzy", seed: int = 0, **kw) -> FLSimConfig:
    return FLSimConfig(
        scheme=scheme, n_rounds=N_ROUNDS, local_epochs=1,
        samples_per_class=260, probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N_CLIENTS, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N_CLIENTS, seed=seed), **kw)


def _leaves(sim):
    return [np.asarray(x).copy() for x in jax.tree.leaves(sim.params)]


def _assert_params_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, np.asarray(y))


# --------------------------------------------------------------------------
# sync parity: the degenerate event server IS the round barrier
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["dcs", "ccs-fuzzy"])
def test_event_server_sync_parity_rows_and_params(scheme):
    """ISSUE 6 acceptance: churn off + staleness drop + cadence at the
    round period -> the event-driven server reproduces the serial
    driver's rows and final params bit-identically."""
    sync = FLSimulation(_cfg(scheme))
    event = FLSimulation(_cfg(scheme), run=RunConfig(server="event"))
    assert EventDrivenServer(event).sync_equivalent
    rows_s = sync.run(N_ROUNDS)
    rows_e = event.run(N_ROUNDS)
    assert rows_s == rows_e
    _assert_params_equal(_leaves(sync), jax.tree.leaves(event.params))


def test_event_server_sync_parity_through_sweep():
    """The sweep's seed-vmapped dispatch drives the event server
    through the same finish_round seam: rows identical to the sync
    sweep (the CSV bit-parity pin)."""
    from repro.launch.sweep import run_seed_group

    def tiny_cfg(scheme, classes, dist, seed):
        cfg = _cfg(scheme, seed=seed)
        cfg.mobility = MobilityConfig(n_vehicles=N_CLIENTS,
                                      distribution=dist, seed=seed)
        return cfg

    a = run_seed_group("dcs", 9, "uniform", [0, 1], 2, cfg_fn=tiny_cfg)
    b = run_seed_group("dcs", 9, "uniform", [0, 1], 2, cfg_fn=tiny_cfg,
                       run=RunConfig(server="event"))
    assert a == b


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import numpy as np
import jax
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding

N = 10                                   # not divisible by 4

def cfg(seed=0):
    return FLSimConfig(
        scheme="dcs", n_rounds=2, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N, seed=seed))

mesh = make_clients_mesh(4)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    sync = FLSimulation(cfg())
    event = FLSimulation(cfg(), run=RunConfig(server="event"))
    assert sync.client_mesh is not None and sync.n_shards == 4
    rows_s = sync.run(2)
    rows_e = event.run(2)
    assert rows_s == rows_e, "event rows diverge on the clients mesh"
    for a, b in zip(jax.tree.leaves(sync.params),
                    jax.tree.leaves(event.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print(json.dumps({"ok": True, "n_sel": int(sum(r["n_selected"]
                                               for r in rows_s))}))
"""


def test_event_server_sync_parity_on_forced_mesh():
    """Same pin on a forced 4-device clients mesh (N % 4 != 0 padding):
    the event server's delegation must preserve the sharded trainer's
    psum'd FedAvg bit-for-bit."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=1500)
    assert proc.returncode == 0, \
        f"event mesh parity child failed:\n{proc.stderr[-4000:]}"
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["ok"] and data["n_sel"] > 0


# --------------------------------------------------------------------------
# churn edge cases
# --------------------------------------------------------------------------

def test_coverage_active_window():
    pos = np.array([0.0, 400.0, 800.0, 999.0])
    got = np.asarray(coverage_active(jnp.asarray(pos), road_length_m=1000.0,
                                     churn_rate=0.2))
    np.testing.assert_array_equal(got, [True, True, False, False])
    assert np.asarray(coverage_active(jnp.asarray(pos),
                                      road_length_m=1000.0,
                                      churn_rate=0.0)).all()


def test_all_departed_round_is_noop_broadcast():
    """churn_rate=1.0 empties the coverage window: nobody probes, nobody
    is selected, and the global model broadcast is a bit-exact no-op."""
    sim = FLSimulation(_cfg(), run=RunConfig(churn_rate=1.0))
    before = _leaves(sim)
    rows = sim.run(2)
    for row in rows:
        assert row["n_active"] == 0
        assert row["n_selected"] == 0
        assert row["n_aggregated"] == 0
    _assert_params_equal(before, jax.tree.leaves(sim.params))


def test_all_survivor_stragglers_wait_for_cadence_tick():
    """A deadline below every client's completion time makes the whole
    cohort stragglers: weighted mode still trains them, but their
    updates only land at a later cadence tick — round 0 aggregates
    nothing (params bit-unchanged), a later round folds them in with a
    discounted weight."""
    probe = FLSimulation(_cfg())
    host = jax.device_get(probe.selection_state(0))
    sel = np.asarray(host["mask"]) > 0
    assert sel.any()
    dur = np.asarray(host["t_done"], np.float64)[sel]   # t_s = 0 at r=0
    period = 0.9 * float(dur.min())                     # all miss Eq. 6

    sim = FLSimulation(_cfg(deadline_s=period),
                       run=RunConfig(staleness="weighted",
                                     staleness_lambda=1.0))
    srv = EventDrivenServer(sim)
    before = _leaves(sim)
    row0 = srv.finish_round(0, srv.selection_state(0))
    assert row0["n_selected"] > 0
    assert row0["n_straggler"] == row0["n_selected"]
    assert row0["n_aggregated"] == 0
    _assert_params_equal(before, jax.tree.leaves(sim.params))

    n_rounds = int(np.ceil(dur.max() / period)) + 2
    rows = [srv.finish_round(r, srv.selection_state(r))
            for r in range(1, n_rounds)]
    landed = [r for r in rows if r["n_aggregated"] > 0]
    assert landed, "straggler updates never landed at a cadence tick"
    assert any(r["stale_frac"] > 0.0 for r in landed)
    for r in landed:
        if r["stale_frac"] > 0.0:       # a stale update is discounted
            assert r["n_effective"] < r["n_aggregated"]


def test_departing_mid_training_drops_pending_update():
    """A client out of coverage at its own upload-completion instant
    loses the update: with every ``alive_at_done`` forced False the
    dispatch enqueues nothing and the global model stays bit-exact."""
    sim = FLSimulation(_cfg(), run=RunConfig(churn_rate=0.2,
                                             staleness="weighted",
                                             staleness_lambda=0.5))
    srv = EventDrivenServer(sim)
    host = jax.device_get(srv.selection_state(0))
    host = {k: np.asarray(v) for k, v in host.items()}
    assert (np.asarray(host["mask"]) > 0).any()
    host["alive_at_done"] = np.zeros(N_CLIENTS, bool)
    before = _leaves(sim)
    srv._dispatch_training(0, host)
    assert not srv._pending
    assert srv._stats[0]["n_agg"] == 0
    _assert_params_equal(before, jax.tree.leaves(sim.params))


# --------------------------------------------------------------------------
# staleness weight (property)
# --------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
# lambda is 0 or at least 1e-3: a subnormal lambda rounds 1 + lambda*delay
# to 1.0 at every delay, and the strict decrease below is then not defined
@given(st.one_of(st.just(0.0), st.floats(1e-3, 10.0)), st.integers(0, 30),
       st.integers(0, 30))
def test_staleness_weight_monotone(lam, d1, d2):
    """1/(1 + lambda*delay): in (0, 1], exactly 1 when fresh or when
    lambda is 0, and non-increasing in the delay."""
    lo, hi = sorted((d1, d2))
    w_lo, w_hi = staleness_weight(lam, lo), staleness_weight(lam, hi)
    assert 0.0 < w_hi <= w_lo <= 1.0
    assert staleness_weight(lam, 0) == 1.0
    assert staleness_weight(0.0, hi) == 1.0
    if lam > 0 and hi > lo:
        assert w_hi < w_lo


def test_staleness_weight_rejects_negative():
    with pytest.raises(ValueError):
        staleness_weight(-0.5, 1)
    with pytest.raises(ValueError):
        staleness_weight(1.0, -1)


# --------------------------------------------------------------------------
# scheme registry
# --------------------------------------------------------------------------

def test_unknown_scheme_raises_with_registered_list():
    with pytest.raises(ValueError, match=r"registered: .*dcs"):
        get_scheme("fedprox")
    with pytest.raises(ValueError, match="unknown selection scheme"):
        FLSimulation(_cfg(scheme="fedprox"))


def test_duplicate_registration_raises():
    with pytest.raises(ValueError, match="already registered"):
        register_scheme("dcs", lambda cfg, pos, evals, key: evals)
    assert get_scheme("dcs").overhead_key == "dcs"   # builtin untouched


def test_custom_scheme_runs_through_simulation():
    """A scheme registered at runtime drives a full round (the registry
    is the only coupling point between pipeline and scheme)."""
    def first_k(cfg, pos, evals, sel_key):
        return (jnp.arange(cfg.n_clients)
                < cfg.n_clients_central).astype(jnp.int32)

    register_scheme("first-k", first_k, overhead_key="cfl")
    try:
        assert "first-k" in scheme_names()
        sim = FLSimulation(_cfg(scheme="first-k"))
        row = sim.run_round(0)
        assert row["n_selected"] == sim.stage_cfg.n_clients_central
        picked = np.where(sim.last_mask > 0)[0]
        assert picked.max() < sim.stage_cfg.n_clients_central
    finally:
        schemes._REGISTRY.pop("first-k", None)


# --------------------------------------------------------------------------
# RunConfig surface + deprecation shim
# --------------------------------------------------------------------------

def test_runconfig_promotes_and_validates():
    assert RunConfig().resolved().server == "sync"
    assert RunConfig(churn_rate=0.3).resolved().server == "event"
    assert RunConfig(staleness="weighted").resolved().server == "event"
    assert RunConfig(agg_cadence_s=5.0).resolved().server == "event"
    with pytest.raises(ValueError):
        RunConfig(churn_rate=1.5).resolved()
    with pytest.raises(ValueError):
        RunConfig(staleness="sometimes").resolved()
    with pytest.raises(ValueError):
        RunConfig(agg_cadence_s=0.0).resolved()
    with pytest.raises(ValueError):      # weighted needs the batched engine
        RunConfig(staleness="weighted", engine="loop").resolved()


def test_deprecated_sim_kwargs_warn_but_work():
    """FLSimConfig.engine/fused_probe/overlap_rounds still work for one
    release: a DeprecationWarning fires and the value lands on the
    resolved RunConfig."""
    with pytest.warns(DeprecationWarning, match="FLSimConfig.engine"):
        sim = FLSimulation(_cfg(engine="loop"))
    assert sim.run_cfg.engine == "loop"
    with pytest.warns(DeprecationWarning, match="fused_probe"):
        sim = FLSimulation(_cfg(fused_probe=False))
    assert not sim.run_cfg.fused_probe
    assert not sim.stage_cfg.fused_probe
    with pytest.warns(DeprecationWarning, match="overlap_rounds"):
        sim = FLSimulation(_cfg(overlap_rounds=False))
    assert not sim.run_cfg.overlap_rounds


def test_runconfig_from_args_compat_flags():
    import argparse

    from repro.fl.runconfig import add_run_arguments

    ap = argparse.ArgumentParser()
    add_run_arguments(ap)
    run = RunConfig.from_args(ap.parse_args([]))
    assert run.fused_probe and run.overlap_rounds and run.server == "sync"
    run = RunConfig.from_args(ap.parse_args(
        ["--compat-aligned-pack", "--no-overlap-rounds"]))
    assert not run.fused_probe and not run.overlap_rounds
    run = RunConfig.from_args(ap.parse_args(
        ["--churn-rate", "0.3", "--staleness", "weighted",
         "--staleness-lambda", "1.5", "--agg-cadence", "0"]))
    assert run.server == "event" and run.agg_cadence_s is None
    assert run.churn_rate == 0.3 and run.staleness_lambda == 1.5


# --------------------------------------------------------------------------
# full event fleet smoke (churn x weighted staleness x sub-round cadence)
# --------------------------------------------------------------------------

def test_event_fleet_smoke_deterministic():
    """Churn + weighted staleness + a sub-round cadence: rows stay
    internally consistent (histogram sums to the aggregate count, the
    effective cohort never exceeds it) and the whole run is
    deterministic across two fresh simulations."""
    run = RunConfig(churn_rate=0.3, staleness="weighted",
                    staleness_lambda=1.0, agg_cadence_s=30.0)

    def go():
        sim = FLSimulation(_cfg(), run=run)
        return sim.run(N_ROUNDS)

    rows = go()
    for row in rows:
        assert 0 <= row["n_active"] <= N_CLIENTS
        assert 0.0 <= row["stale_frac"] <= 1.0
        hist = [int(h) for h in row["rounds_behind_hist"].split("/")]
        assert len(hist) == 4 and sum(hist) == row["n_aggregated"]
        assert row["n_effective"] <= row["n_aggregated"] + 1e-9
    assert any(row["n_active"] < N_CLIENTS for row in rows)
    assert rows == go()
