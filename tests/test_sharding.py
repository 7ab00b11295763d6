"""Mesh-sharded client axis (ISSUE 4 acceptance).

The heavy parity checks run in a subprocess with 8 forced CPU host
devices (the device count is fixed at jax backend init, so it cannot be
raised inside an already-running pytest process): on 4- and 8-device
client meshes the shard_map'd ``selection_prefix_sharded`` must emit
selection masks *bit-identical* to the single-device staged pipeline,
and a round completed through the sharded grouped trainer must match
the unsharded global params within 1e-5 — including an
N-not-divisible-by-mesh padding case and an empty-survivor round.

The in-process tests cover the host-side satellite surface: strict /
logged ``resolve_pspec``, the clients-mesh constructors, the launcher
mesh-spec parsing, sharded cohort bucketing and the psum'd FedAvg.
"""
import json
import logging
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.fl import pipeline
from repro.fl.aggregation import fedavg_masked, fedavg_sums
from repro.launch.mesh import (client_mesh_context, make_clients_mesh,
                               make_debug_mesh, parse_mesh_spec)
from repro.sharding.api import resolve_pspec, sweep_devices

REPO = Path(__file__).resolve().parent.parent

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
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding, \
    sweep_devices

N = 10                                   # not divisible by 4 or 8:
                                         # every mesh pads dummy clients

def cfg(scheme, seed=0, **kw):
    return FLSimConfig(
        scheme=scheme, n_rounds=2, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N, seed=seed), **kw)

def leaves(p):
    return [np.asarray(x) for x in jax.tree.leaves(p)]

def max_ulps(p, q):
    # the largest gap of each leaf in ulps of the leaf's largest magnitude
    return max(float(np.max(np.abs(a - b)) / np.spacing(np.max(np.abs(a))))
               for a, b in zip(leaves(p), leaves(q)))

def run_case(scheme, k, rounds, **kw):
    # ``sh`` carries its own params from round to round; ``step`` starts
    # every round from the reference's params, so it compares one sharded
    # round with one plain round.  The two aggregate in another float order
    # (per-shard sums, then a psum), which costs a few ulps (checked at
    # round 0); a later local SGD round amplifies such ulps through ReLU and
    # max-pool switches, so the carried params are held to the same masks,
    # survivors and evaluations, and ``step`` to the 1e-5 params bound.
    ref = FLSimulation(cfg(scheme, **kw))
    mesh = make_clients_mesh(k)
    with mesh, logical_sharding(mesh, DEFAULT_RULES):
        assert len(sweep_devices()) == 1        # one placement domain
        sh = FLSimulation(cfg(scheme, **kw))
        step = FLSimulation(cfg(scheme, **kw))
        assert sh.client_mesh is not None and sh.n_shards == k
        n_sel = 0
        for r in range(rounds):
            step.params = ref.params
            a = jax.device_get(ref.selection_state(r))
            for sim, tag in ((sh, "carried"), (step, "step")):
                b = jax.device_get(sim.selection_state(r))
                np.testing.assert_array_equal(
                    np.asarray(a["mask"]), np.asarray(b["mask"]),
                    err_msg=f"{scheme} k={k} round {r} {tag}: masks diverge")
                np.testing.assert_array_equal(np.asarray(a["survivors"]),
                                              np.asarray(b["survivors"]))
                np.testing.assert_allclose(np.asarray(a["evals"]),
                                           np.asarray(b["evals"]),
                                           rtol=1e-4, atol=1e-3)
                assert int(a["n_straggler"]) == int(b["n_straggler"])
                assert int(a["n_selected"]) == int(b["n_selected"])
                if sim is sh:
                    rb = sh.finish_round(r, b)
                else:
                    rs = step.finish_round(r, b)
            ra = ref.finish_round(r, a)
            for la, lb in zip(leaves(ref.params), leaves(step.params)):
                np.testing.assert_allclose(
                    la, lb, atol=1e-5,
                    err_msg=f"{scheme} k={k} round {r}: params diverge")
            assert abs(ra["accuracy"] - rs["accuracy"]) <= 1e-5
            if r == 0:
                assert max_ulps(ref.params, sh.params) <= 4
                assert abs(ra["accuracy"] - rb["accuracy"]) <= 1e-5
            n_sel += int(b["n_selected"])
        return n_sel

def run_seeds_case(k):
    # the seed-vmapped prefix, sharded vs unsharded on identical inputs
    import jax.numpy as jnp
    from repro.fl import pipeline
    mesh = make_clients_mesh(k)
    with mesh, logical_sharding(mesh, DEFAULT_RULES):
        sims = [FLSimulation(cfg("dcs")), FLSimulation(cfg("dcs",
                                                           seed=1))]
        st = pipeline.stack_statics([s.statics for s in sims])
        params = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[s.params for s in sims])
        sel = jnp.stack([s.key for s in sims])
        net = jnp.stack([s.net_key for s in sims])
        cfg0 = sims[0].stage_cfg
        a = jax.device_get(pipeline.selection_prefix_seeds(
            st, params, jnp.int32(0), sel, net, cfg=cfg0))
        b = jax.device_get(pipeline.selection_prefix_seeds_sharded(
            st, params, jnp.int32(0), sel, net, cfg=cfg0, mesh=mesh))
        np.testing.assert_array_equal(np.asarray(a["mask"]),
                                      np.asarray(b["mask"]))
        np.testing.assert_array_equal(np.asarray(a["survivors"]),
                                      np.asarray(b["survivors"]))
        np.testing.assert_allclose(np.asarray(a["evals"]),
                                   np.asarray(b["evals"]),
                                   rtol=1e-4, atol=1e-3)
        return int(np.asarray(b["mask"]).sum())

out = {}
out["dcs_k4"] = run_case("dcs", 4, rounds=2)
out["dcs_k8"] = run_case("dcs", 8, rounds=1)
out["random_k4"] = run_case("random", 4, rounds=1)
out["ccs_fuzzy_k8"] = run_case("ccs-fuzzy", 8, rounds=1)
out["seeds_k4"] = run_seeds_case(4)
# empty-survivor round: nobody clears E_tau, both paths no-op broadcast
assert run_case("dcs", 4, rounds=1, e_tau=1e9) == 0
out["ok"] = True
print(json.dumps(out))
"""


def test_sharded_parity_on_forced_4_and_8_device_mesh():
    """ISSUE 4 acceptance: bit-identical masks + <=1e-5 params on 4- and
    8-device CPU client meshes, with client padding and an empty round."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=1500)
    assert proc.returncode == 0, \
        f"sharded parity child failed:\n{proc.stderr[-4000:]}"
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["ok"]
    # the sharded rounds actually selected clients (non-degenerate)
    assert data["dcs_k4"] > 0 and data["dcs_k8"] > 0


# -- in-process satellite coverage ------------------------------------------

def _mesh1(axis="clients"):
    return Mesh(np.asarray(jax.devices()[:1]), (axis,))


def test_resolve_pspec_require_raises_on_indivisible():
    mesh = _mesh1()
    with pytest.raises(ValueError, match="clients"):
        resolve_pspec(mesh, {"clients": "clients"}, ("clients",), (10,),
                      require=("clients",))


def test_resolve_pspec_require_raises_without_rule():
    mesh = _mesh1()
    with pytest.raises(ValueError, match="no rule"):
        resolve_pspec(mesh, {}, ("clients",), (8,), require=("clients",))


def test_resolve_pspec_warns_on_nondivisible_drop(caplog):
    mesh = _mesh1("data")
    # 'data' has size 1 here, so force the non-divisible branch with a
    # fake 2-extent via a 2-device mesh if available, else skip
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for a non-divisible drop")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    with caplog.at_level(logging.WARNING, logger="repro.sharding.api"):
        spec = resolve_pspec(mesh, {"batch": "data"}, ("batch",), (7,))
    assert spec == P(None)
    assert any("batch" in rec.message for rec in caplog.records)


def test_resolve_pspec_divisible_still_shards():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("clients",))
    spec = resolve_pspec(mesh, {"clients": "clients"}, ("clients", None),
                         (8, 3), require=("clients",))
    assert spec == P("clients", None)


def test_make_debug_mesh_raises_value_error():
    with pytest.raises(ValueError, match="not divisible"):
        make_debug_mesh(n_devices=1, model=3)


def test_make_clients_mesh_too_many_devices():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_clients_mesh(len(jax.devices()) + 1)


def test_make_clients_mesh_axis():
    mesh = make_clients_mesh(1)
    assert dict(mesh.shape) == {"clients": 1}


def test_parse_mesh_spec():
    assert parse_mesh_spec("clients=8") == {"clients": 8}
    with pytest.raises(ValueError):
        parse_mesh_spec("clients")
    with pytest.raises(ValueError):
        parse_mesh_spec("clients=x")


def test_client_mesh_context_rejects_unknown_axis():
    with pytest.raises(ValueError, match="unknown mesh axes"):
        with client_mesh_context("model=2"):
            pass


def test_client_mesh_context_none_is_noop():
    with client_mesh_context(None) as mesh:
        assert mesh is None
    assert pipeline.active_client_mesh() is None


def test_sweep_devices_without_mesh_lists_devices():
    assert len(sweep_devices()) == len(jax.devices())


def test_cohort_bucket_sharded():
    assert pipeline.cohort_bucket_sharded(3, 1) == 4   # == cohort_bucket
    assert pipeline.cohort_bucket_sharded(1, 4) == 4   # floor 2, pad to 4
    assert pipeline.cohort_bucket_sharded(5, 4) == 8
    assert pipeline.cohort_bucket_sharded(5, 8) == 8
    assert pipeline.pad_to_shards(10, 4) == 12


def test_fedavg_masked_axis_name_matches_unsharded():
    """The psum'd FedAvg (shard_map over a clients mesh) equals the
    plain masked FedAvg."""
    rng = np.random.default_rng(0)
    stacked = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
               "b": jnp.asarray(rng.normal(size=(4,)).astype(np.float32))}
    weights = jnp.asarray([120.0, 40.0, 0.0, 40.0])
    mesh = _mesh1()
    sharded = jax.shard_map(
        lambda s, w: fedavg_masked(s, w, axis_name="clients"), mesh=mesh,
        in_specs=(P("clients"), P("clients")), out_specs=P(),
        check_vma=False)
    got = sharded(stacked, weights)
    want = fedavg_masked(stacked, weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_fedavg_sums_matches_masked():
    rng = np.random.default_rng(1)
    stacked = {"w": jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32))}
    weights = jnp.asarray([10.0, 0.0, 30.0])
    num, den = fedavg_sums(stacked, weights)
    want = fedavg_masked(stacked, weights)
    np.testing.assert_allclose(np.asarray(num["w"]) / float(den),
                               np.asarray(want["w"]), rtol=1e-6)
