"""Windowed neighbour-exchange DCS election (ISSUE 9 acceptance).

Parity is THE invariant: whenever the windowed election reports
``overflow == 0`` its mask must be bit-identical to the dense
``neighbor_elect_ref`` on the same floats — across ties, duplicate
positions, undersized windows, churned fleets and ``N % K != 0``
padding.  The property suite pins the single-device windowed path
(jnp + pallas-interpret) against both the dense reference and the
windowed oracle (which additionally certifies the no-under-flagging
contract); the subprocess test pins the shard_map'd ring-halo election
(forced 4- and 8-device meshes) and the driver's gather fallback on a
forced buffer overflow.

Satellite coverage rides along: the adaptive ``_pick_blocks`` lane
picker for the dense Pallas kernel, the ``shard_client_range`` per-host
loading helper, the windowed RunConfig knobs, and the persistent jit
compilation cache.
"""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.elect import auto_capacity, auto_window, windowed_elect
from repro.core.selection import dcs_select, dcs_select_windowed
from repro.fl.partition import shard_client_range
from repro.fl.runconfig import AUTO_WINDOWED_MIN_CLIENTS, RunConfig
from repro.kernels.neighbor_elect import _pick_blocks
from repro.kernels.ref import neighbor_elect_ref, windowed_elect_ref
from repro.launch.cache import resolve_cache_dir

REPO = Path(__file__).resolve().parent.parent


# -- adaptive dense-kernel blocks (satellite) --------------------------------

def test_pick_blocks_small_fleet_stops_padding():
    """A 96-vehicle fleet must land on 128 lanes, not 1024."""
    bi, bj, np_ = _pick_blocks(96)
    assert np_ == 128 and bi <= 128 and bj <= 128
    assert np_ % bi == 0 and np_ % bj == 0


@pytest.mark.parametrize("n", [1, 30, 96, 128, 129, 256, 1000, 1024, 2048])
def test_pick_blocks_invariants(n):
    bi, bj, np_ = _pick_blocks(n)
    assert np_ >= n and np_ % 128 == 0
    assert np_ % bi == 0 and np_ % bj == 0     # whole grid steps
    assert np_ - n < 128                        # minimal 128-padding


def test_pick_blocks_large_keeps_tuned_tiles():
    bi, bj, np_ = _pick_blocks(2048)
    assert (bi, bj, np_) == (256, 1024, 2048)


@pytest.mark.parametrize("n", [5, 96, 130])
def test_dense_pallas_adaptive_blocks_match_ref(n):
    rng = np.random.default_rng(n)
    pos = jnp.asarray(rng.uniform(0, 1000, n).astype(np.float32))
    ev = jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
    from repro.kernels.neighbor_elect import neighbor_elect_pallas
    got = neighbor_elect_pallas(pos, ev, comm_range=200.0, top_m=2,
                                e_tau=30.0, interpret=True)
    want = neighbor_elect_ref(pos, ev, comm_range=200.0, top_m=2,
                              e_tau=30.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- single-device windowed parity (tentpole, property suite) ----------------

def _check_windowed(pos, ev, *, comm_range, top_m, e_tau, window, impl):
    pos = jnp.asarray(pos, jnp.float32)
    ev = jnp.asarray(ev, jnp.float32)
    mask, ovf = windowed_elect(pos, ev, comm_range=comm_range, top_m=top_m,
                               e_tau=e_tau, window=window, impl=impl)
    omask, oovf = windowed_elect_ref(pos, ev, comm_range=comm_range,
                                     top_m=top_m, e_tau=e_tau,
                                     window=window)
    dense = neighbor_elect_ref(pos, ev, comm_range=comm_range, top_m=top_m,
                               e_tau=e_tau)
    # the oracle's own contract (dense mask; overflow from rank distance)
    np.testing.assert_array_equal(np.asarray(omask), np.asarray(dense))
    # no under-flagging: the impl must flag whenever the oracle does
    assert int(ovf) >= int(oovf), \
        f"impl={impl} window={window}: under-flagged overflow"
    if int(ovf) == 0:
        np.testing.assert_array_equal(
            np.asarray(mask), np.asarray(dense),
            err_msg=f"impl={impl} window={window}: mask != dense with "
                    f"overflow=0")


@settings(max_examples=40, deadline=None)
@given(st.integers(4, 40), st.integers(0, 10**6),
       st.integers(1, 44), st.sampled_from([50.0, 200.0, 1000.0]),
       st.sampled_from([0.0, 30.0, 101.0]), st.integers(1, 3),
       st.sampled_from(["jnp", "pallas"]))
def test_windowed_matches_dense_or_flags(n, seed, window, comm_range,
                                         e_tau, top_m, impl):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 1000.0, n).astype(np.float32)
    ev = rng.uniform(0, 100.0, n).astype(np.float32)
    if seed % 3 == 0:            # duplicate positions (sort-tie stress)
        pos = np.round(pos, -1)
    if seed % 4 == 0:            # eval ties (index tie-break stress)
        ev = np.round(ev, -1)
    _check_windowed(pos, ev, comm_range=comm_range, top_m=top_m,
                    e_tau=e_tau, window=window, impl=impl)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_windowed_all_tied_evals(impl):
    """Every eval identical: selection is decided purely by the index
    tie-break — the hardest bit-parity case."""
    n = 24
    rng = np.random.default_rng(7)
    pos = rng.uniform(0, 300.0, n).astype(np.float32)
    ev = np.full(n, 50.0, np.float32)
    for window in (1, 4, n + 1):
        _check_windowed(pos, ev, comm_range=200.0, top_m=2, e_tau=30.0,
                        window=window, impl=impl)


def test_windowed_empty_fleet_below_threshold():
    """Nobody clears e_tau: mask all-zero, never an overflow (there is
    no comparison the window could have missed that matters)."""
    pos = jnp.asarray(np.linspace(0, 100, 16), jnp.float32)
    ev = jnp.full((16,), 5.0, jnp.float32)
    mask, ovf = windowed_elect(pos, ev, comm_range=200.0, top_m=2,
                               e_tau=30.0, window=2)
    assert int(mask.sum()) == 0


def test_dcs_select_windowed_full_window_equals_dense():
    n = 30
    rng = np.random.default_rng(3)
    pos = jnp.asarray(rng.uniform(0, 1000, n).astype(np.float32))
    ev = jnp.asarray(rng.uniform(0, 100, n).astype(np.float32))
    mask, ovf = dcs_select_windowed(pos, ev, window=n)
    assert int(ovf) == 0
    np.testing.assert_array_equal(np.asarray(mask),
                                  np.asarray(dcs_select(pos, ev)))


# -- sizing helpers + config plumbing (satellites) ---------------------------

def test_auto_window_scales_with_density_not_fleet():
    # fixed density: the window is flat in N
    assert auto_window(10_000, 200.0, 10_000.0) \
        == auto_window(100_000, 200.0, 100_000.0)
    # denser road -> bigger window, clamped to the fleet
    assert auto_window(1000, 200.0, 500.0) == 1000
    # the 16 floor dominates tiny fleets (oversized windows are clipped
    # to the array downstream, so this only buys safety)
    assert auto_window(8, 200.0, 1e9) == 16


def test_auto_capacity_bounds():
    assert auto_capacity(64, 8) == 32        # 2*8 + 16
    assert auto_capacity(8, 8) == 8          # never beyond the shard


def test_shard_client_range_partitions_exactly():
    for n, k in [(30, 8), (10, 4), (16, 16), (7, 3), (5, 8)]:
        seen = []
        for d in range(k):
            seen.extend(shard_client_range(n, k, d))
        assert seen == list(range(n)), (n, k)
    assert list(shard_client_range(5, 8, 7)) == []    # empty tail shard
    with pytest.raises(ValueError):
        shard_client_range(10, 4, 4)


def test_runconfig_elect_auto_resolution():
    small = RunConfig().to_stage_config(
        _min_cfg(), n_clients=AUTO_WINDOWED_MIN_CLIENTS - 1)
    big = RunConfig().to_stage_config(
        _min_cfg(), n_clients=AUTO_WINDOWED_MIN_CLIENTS)
    assert small.elect == "gather" and big.elect == "windowed"
    forced = RunConfig(elect="windowed", elect_window=7).to_stage_config(
        _min_cfg(), n_clients=8)
    assert forced.elect == "windowed" and forced.elect_window == 7
    with pytest.raises(ValueError):
        RunConfig(elect="bogus").resolved()


def _min_cfg():
    from repro.fl.rounds import FLSimConfig
    return FLSimConfig(scheme="dcs")


def test_resolve_cache_dir_default_and_disable(monkeypatch, tmp_path):
    """Flag > ``JAX_COMPILATION_CACHE_DIR`` > the fixed checkout-root
    default, which depends on neither the cwd nor an output path."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    assert resolve_cache_dir(None) == str(REPO / ".jit-cache")
    assert resolve_cache_dir("none") is None
    assert resolve_cache_dir("") is None
    assert resolve_cache_dir("/d") == "/d"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env")
    assert resolve_cache_dir(None) == "/env"
    assert resolve_cache_dir("/d") == "/d"
    assert resolve_cache_dir("none") is None


def test_jit_cache_populates(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, the launcher's cache
    wiring persists CPU executables there (the default thresholds would
    skip them) and points jax at no other directory — run a tiny jit in
    a subprocess and check that directory gained entries."""
    cache = tmp_path / "jc"
    child = (
        "from repro.launch.cache import enable_jit_cache, "
        "resolve_cache_dir\n"
        "print(enable_jit_cache(resolve_cache_dir(None)))\n"
        "import jax, jax.numpy as jnp\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(int(jax.jit(lambda x: (x * 3 + 1).sum())"
        "(jnp.arange(128.0))))\n")
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(cache)}
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, env=env,
                          cwd=tmp_path, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(cache), str(cache), "24512"]
    assert cache.is_dir() and any(cache.iterdir()), \
        "persistent jit cache stayed empty"


def test_multihost_arg_plumbing():
    import argparse

    from repro.launch.multihost import (add_multihost_arguments,
                                        multihost_from_args, should_spawn)
    ap = argparse.ArgumentParser()
    add_multihost_arguments(ap)
    parent = ap.parse_args(["--multihost", "2"])
    assert should_spawn(parent) and multihost_from_args(parent) is None
    child = ap.parse_args(["--multihost", "2", "--_mh-coord",
                           "127.0.0.1:9999", "--_mh-procs", "2",
                           "--_mh-proc-id", "1"])
    assert not should_spawn(child)
    assert multihost_from_args(child) == ("127.0.0.1:9999", 2, 1)
    assert not should_spawn(ap.parse_args([]))


# -- sharded ring-halo parity + driver fallback (subprocess) -----------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import dataclasses
import json
import numpy as np
import jax
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding

def cfg(scheme, n, seed=0, **kw):
    return FLSimConfig(
        scheme=scheme, n_rounds=2, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=n, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=n, seed=seed), **kw)

def states(scheme, n, k, run, rounds=2, **kw):
    if k == 0:
        sim = FLSimulation(cfg(scheme, n, **kw), run=run)
        return [jax.device_get(sim.resolve_elect_overflow(
            r, jax.device_get(sim.selection_state(r))))
            for r in range(rounds)], sim
    mesh = make_clients_mesh(k)
    with mesh, logical_sharding(mesh, DEFAULT_RULES):
        sim = FLSimulation(cfg(scheme, n, **kw), run=run)
        return [jax.device_get(sim.resolve_elect_overflow(
            r, jax.device_get(sim.selection_state(r))))
            for r in range(rounds)], sim

out = {"ok": False}
gather = RunConfig(elect="gather")
windowed = RunConfig(elect="windowed")

# windowed == gather == unsharded, N % K != 0 padding, churn on/off,
# across forced 4- and 8-device meshes and both N=10 and N=30
n_windowed_sel = 0
for scheme in ("dcs", "ccs-fuzzy", "random"):
    for n, k, churn in [(10, 4, 0.0), (10, 8, 0.3), (30, 8, 0.0),
                        (30, 4, 0.3)]:
        rg = dataclasses.replace(gather, churn_rate=churn).resolved()
        rw = dataclasses.replace(windowed, churn_rate=churn).resolved()
        a, _ = states(scheme, n, 0, rg)
        b, _ = states(scheme, n, k, rg)
        c, simw = states(scheme, n, k, rw)
        for r, (sa, sb, sc) in enumerate(zip(a, b, c)):
            np.testing.assert_array_equal(
                np.asarray(sa["mask"]), np.asarray(sb["mask"]),
                err_msg=f"{scheme} n={n} k={k} r={r}: gather != unsharded")
            np.testing.assert_array_equal(
                np.asarray(sa["mask"]), np.asarray(sc["mask"]),
                err_msg=f"{scheme} n={n} k={k} r={r}: windowed != dense")
            assert int(sa["n_selected"]) == int(sc["n_selected"])
            n_windowed_sel += int(np.asarray(sc["mask"]).sum())
out["windowed_selected"] = n_windowed_sel
assert n_windowed_sel > 0, "degenerate: windowed never selected anyone"

# eval ties at shard boundaries: a constant-eval fleet forces every
# decision through the global-index tie-break across the halo exchange
mesh = make_clients_mesh(8)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    import jax.numpy as jnp
    from repro.core.elect import (auto_capacity, auto_window,
                                  ring_halo_elect)
    from jax.sharding import PartitionSpec as P
    from repro.kernels.ref import neighbor_elect_ref
    n, k, road = 64, 8, 400.0
    rng = np.random.default_rng(11)
    for tie in (False, True):
        pos = rng.uniform(0, road, n).astype(np.float32)
        ev = (np.full(n, 55.0, np.float32) if tie
              else rng.uniform(0, 100, n).astype(np.float32))
        def body(p, e, g, v):
            m_, o_ = ring_halo_elect(
                p, e, g, v, axis="clients", n=n, n_shards=k,
                shard_n=n // k, comm_range=120.0, top_m=2, e_tau=30.0,
                road_length=road, window=auto_window(n, 120.0, road),
                capacity=auto_capacity(n // k, k))
            return m_, jax.lax.pmax(o_, "clients")
        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("clients"),) * 4,
                       out_specs=(P("clients"), P()))
        mask, ovf = fn(jnp.asarray(pos), jnp.asarray(ev),
                       jnp.arange(n, dtype=jnp.int32),
                       jnp.ones(n, bool))
        assert int(ovf) == 0, f"tie={tie}: unexpected overflow"
        dense = neighbor_elect_ref(jnp.asarray(pos), jnp.asarray(ev),
                                   comm_range=120.0, top_m=2, e_tau=30.0)
        np.testing.assert_array_equal(
            np.asarray(mask), np.asarray(dense),
            err_msg=f"boundary ties tie={tie}: ring halo != dense")

# forced overflow (capacity=1): the prefix must FLAG, and the driver
# fallback must land on the bit-exact dense masks
mesh = make_clients_mesh(8)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    sim = FLSimulation(cfg("dcs", 30), run=windowed)
    sim.stage_cfg = dataclasses.replace(sim.stage_cfg, elect_capacity=1)
    raw = jax.device_get(sim.selection_state(0))
    assert int(np.max(raw["elect_overflow"])) == 1, \
        "capacity=1 did not raise the overflow flag"
    fixed = sim.resolve_elect_overflow(0, raw)
    ref = FLSimulation(cfg("dcs", 30), run=gather)
    want = jax.device_get(ref.selection_state(0))
    np.testing.assert_array_equal(np.asarray(fixed["mask"]),
                                  np.asarray(want["mask"]))
out["overflow_fallback"] = True

out["ok"] = True
print(json.dumps(out))
"""


@pytest.mark.slow
def test_windowed_sharded_parity_and_fallback():
    """Tentpole acceptance: ring-halo windowed masks bit-identical to
    the gather election and the unsharded pipeline on forced 4/8-device
    meshes (churn, padding, boundary ties), and the capacity-overflow
    driver fallback reproduces the dense masks exactly."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=2400)
    assert proc.returncode == 0, \
        f"windowed sharded parity child failed:\n{proc.stderr[-4000:]}"
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["ok"] and data["overflow_fallback"]
    assert data["windowed_selected"] > 0
