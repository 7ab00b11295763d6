"""Fused probe->evaluate fast path + round-ahead scheduler (ISSUE 5).

Three layers of parity are pinned:

- kernel: interpret-mode Pallas ``probe_fuzzy_pallas`` vs the jnp fast
  path vs the naive oracle on the same packed inputs — per-client
  losses tight, evaluations within 1e-5 relative;
- pipeline: ``selection_prefix`` with ``fused_probe=True`` (fused op +
  tight probe packing) emits selection masks BIT-IDENTICAL to the
  default staged path, per scheme, across rounds of real training —
  including on forced 4-/8-device client meshes with N % K != 0
  padding (subprocess, like tests/test_sharding.py);
- scheduler: the round-ahead overlapped driver produces rows (and
  masks) bit-identical to the serial driver, single-sim and through the
  sweep's seed-vmapped dispatch.
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

from repro.configs.mnist_cnn import CONFIG as CNN_CFG
from repro.core.fuzzy import FuzzyEvaluator
from repro.core.rules import build_rule_table
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.fuzzy_eval import block_p, fuzzy_eval_pallas
from repro.models.cnn import init_cnn

REPO = Path(__file__).resolve().parent.parent

N_CLIENTS = 10
N_ROUNDS = 2


def _cfg(scheme: str, seed: int = 0, **kw) -> FLSimConfig:
    return FLSimConfig(
        scheme=scheme, n_rounds=N_ROUNDS, local_epochs=1,
        samples_per_class=260, probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N_CLIENTS, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N_CLIENTS, seed=seed), **kw)


# --------------------------------------------------------------------------
# kernel parity
# --------------------------------------------------------------------------

def _packed_fixture():
    rng = np.random.default_rng(0)
    n = 6
    counts = np.array([24, 7, 40, 13, 1, 30])
    s = int(counts.sum())
    ev = FuzzyEvaluator()
    table, levels = build_rule_table()
    return dict(
        n=n,
        images=jnp.asarray(rng.normal(size=(s, 28, 28, 1))
                           .astype(np.float32)),
        labels=jnp.asarray(rng.integers(0, 10, s).astype(np.int32)),
        seg=jnp.asarray(np.repeat(np.arange(n), counts).astype(np.int32)),
        counts=jnp.asarray(counts.astype(np.int32)),
        aux=jnp.asarray(np.abs(rng.normal(size=(n, 3)))
                        .astype(np.float32)) * jnp.asarray([100., 1e6, 1.]),
        params=init_cnn(jax.random.PRNGKey(0), CNN_CFG),
        means=jnp.asarray(ev.cfg.means, jnp.float32),
        sigmas=jnp.asarray(ev.cfg.sigmas, jnp.float32),
        centers=jnp.asarray(ev.level_centers, jnp.float32),
        table=table, levels=levels)


def _probe_fuzzy(fx, impl, **kw):
    return kops.probe_fuzzy(fx["params"], fx["images"], fx["labels"],
                            fx["seg"], fx["counts"], fx["aux"], fx["means"],
                            fx["sigmas"], fx["table"], fx["levels"],
                            fx["centers"], n_clients=fx["n"], batch=32,
                            impl=impl, **kw)


def test_probe_fuzzy_pallas_matches_jnp_and_oracle():
    """ISSUE 5 acceptance: interpret-mode Pallas vs jnp reference within
    1e-5 (relative) on evaluations; raw features tight across impls."""
    fx = _packed_fixture()
    f_jnp, e_jnp = _probe_fuzzy(fx, "jnp")
    f_pal, e_pal = _probe_fuzzy(fx, "pallas")
    f_orc, e_orc = _probe_fuzzy(fx, "oracle")
    np.testing.assert_allclose(np.asarray(e_pal), np.asarray(e_jnp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(e_orc), np.asarray(e_jnp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_jnp),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(f_orc), np.asarray(f_jnp),
                               rtol=1e-5, atol=1e-6)


def test_probe_fuzzy_external_maxima_matches_in_op():
    """The mesh-sharded seam: passing the batch's own column maxima
    externally must reproduce the in-op Eq. 8 normalization."""
    fx = _packed_fixture()
    feats, e_in = _probe_fuzzy(fx, "jnp")
    cm = jnp.asarray(np.asarray(feats).max(axis=0))
    for impl in ("jnp", "pallas", "oracle"):
        _, e_ext = _probe_fuzzy(fx, impl, col_maxima=cm)
        np.testing.assert_allclose(np.asarray(e_ext), np.asarray(e_in),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"impl={impl}")


def test_probe_loss_impls_agree():
    fx = _packed_fixture()
    args = (fx["params"], fx["images"], fx["labels"], fx["seg"],
            fx["counts"])
    l_jnp = kops.probe_loss(*args, n_clients=fx["n"], batch=32, impl="jnp")
    l_pal = kops.probe_loss(*args, n_clients=fx["n"], impl="pallas")
    l_orc = kops.probe_loss(*args, n_clients=fx["n"], impl="oracle")
    np.testing.assert_allclose(np.asarray(l_pal), np.asarray(l_jnp),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_orc), np.asarray(l_jnp),
                               rtol=1e-5, atol=1e-6)


def test_probe_fuzzy_ref_matches_composed_stages():
    """The oracle equals dataset_loss_packed + fuzzy_eval_ref composed —
    the fused op is the same math as the staged path."""
    fx = _packed_fixture()
    lf = kref.probe_loss_ref(fx["params"], fx["images"], fx["labels"],
                             fx["seg"], fx["counts"], n_clients=fx["n"])
    feats = jnp.concatenate([fx["aux"], lf[:, None]], axis=1)
    e_staged = kref.fuzzy_eval_ref(feats, fx["means"], fx["sigmas"],
                                   fx["table"], fx["levels"], fx["centers"],
                                   normalize=True)
    _, e_fused = _probe_fuzzy(fx, "oracle")
    np.testing.assert_allclose(np.asarray(e_fused), np.asarray(e_staged),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# fuzzy_eval block sizing (satellite)
# --------------------------------------------------------------------------

def test_fuzzy_block_adapts_to_small_fleets():
    assert block_p(1) == 128
    assert block_p(96) == 128          # was 1024: a 10.7x dead-lane pad
    assert block_p(129) == 256
    assert block_p(1024) == 1024
    assert block_p(5000) == 1024       # cap holds for big fleets


def test_fuzzy_eval_small_fleet_matches_ref():
    """A 96-client fleet runs in one 128-lane block and still matches
    the reference (padding lanes cannot leak into real ones)."""
    rng = np.random.default_rng(3)
    ev = FuzzyEvaluator()
    table, levels = build_rule_table()
    means = jnp.asarray(ev.cfg.means, jnp.float32)
    sigmas = jnp.asarray(ev.cfg.sigmas, jnp.float32)
    centers = jnp.asarray(ev.level_centers, jnp.float32)
    for p in (5, 96, 200):
        x = jnp.asarray(rng.uniform(0, 1, (p, 4)).astype(np.float32))
        got = fuzzy_eval_pallas(x, means, sigmas, table, levels, centers,
                                interpret=True)
        want = kref.fuzzy_eval_ref(x, means, sigmas, table, levels, centers)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4, err_msg=f"P={p}")


# --------------------------------------------------------------------------
# pipeline parity: fused vs unfused masks, with training in the loop
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["dcs", "ccs-fuzzy", "random"])
def test_fused_prefix_masks_bitwise_vs_unfused(scheme):
    """ISSUE 5 acceptance: selection masks BIT-IDENTICAL fused vs
    unfused through ``selection_prefix``, across rounds with real
    training in between (so round 1 probes evolved params)."""
    ref = FLSimulation(_cfg(scheme), run=RunConfig(fused_probe=False))
    fused = FLSimulation(_cfg(scheme))      # fused is the default now
    assert fused.stage_cfg.fused_probe
    # the tight pack must actually be tighter than the aligned pack
    assert (fused.statics.probe_images.shape[0]
            < ref.statics.probe_images.shape[0])
    for r in range(N_ROUNDS):
        a = jax.device_get(ref.selection_state(r))
        b = jax.device_get(fused.selection_state(r))
        np.testing.assert_array_equal(
            np.asarray(a["mask"]), np.asarray(b["mask"]),
            err_msg=f"{scheme} round {r}: fused mask diverges")
        np.testing.assert_array_equal(np.asarray(a["survivors"]),
                                      np.asarray(b["survivors"]))
        np.testing.assert_allclose(np.asarray(a["evals"]),
                                   np.asarray(b["evals"]),
                                   rtol=1e-4, atol=1e-3)
        ra = ref.finish_round(r, a)
        rb = fused.finish_round(r, b)
        assert abs(ra["accuracy"] - rb["accuracy"]) <= 1e-5


# --------------------------------------------------------------------------
# sharded fused parity (forced 4-/8-device meshes, N % K != 0)
# --------------------------------------------------------------------------

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

N = 10                                   # not divisible by 4 or 8

def cfg(scheme, seed=0, **kw):
    return FLSimConfig(
        scheme=scheme, n_rounds=2, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N, seed=seed), **kw)

def run_case(scheme, k, rounds):
    # ``fused`` and ``sh`` carry their own params from round to round;
    # ``step`` (sharded) starts every round from the plain sim's params.
    # The sharded trainer aggregates in another float order (per-shard
    # sums, then a psum), and a later local SGD round amplifies those few
    # ulps through ReLU and max-pool switches, so the carried sharded sim is
    # held to the masks, survivors and evaluations after round 0, and
    # ``step`` to the round-0 accuracy bound in every round.
    plain = FLSimulation(cfg(scheme),                 # unfused, unsharded
                         run=RunConfig(fused_probe=False))
    fused = FLSimulation(cfg(scheme))                 # fused default
    mesh = make_clients_mesh(k)
    with mesh, logical_sharding(mesh, DEFAULT_RULES):
        sh = FLSimulation(cfg(scheme))
        step = FLSimulation(cfg(scheme))
        assert sh.client_mesh is not None and sh.n_shards == k
        n_sel = 0
        for r in range(rounds):
            step.params = plain.params
            a = jax.device_get(plain.selection_state(r))
            b = jax.device_get(fused.selection_state(r))
            c = jax.device_get(sh.selection_state(r))
            d = jax.device_get(step.selection_state(r))
            for tag, s in (("fused", b), ("fused+sharded", c),
                           ("fused+sharded step", d)):
                np.testing.assert_array_equal(
                    np.asarray(a["mask"]), np.asarray(s["mask"]),
                    err_msg=f"{scheme} k={k} round {r}: {tag} mask")
                np.testing.assert_array_equal(np.asarray(a["survivors"]),
                                              np.asarray(s["survivors"]))
                np.testing.assert_allclose(np.asarray(a["evals"]),
                                           np.asarray(s["evals"]),
                                           rtol=1e-4, atol=1e-3)
            ra = plain.finish_round(r, a)
            rb = fused.finish_round(r, b)
            rc = sh.finish_round(r, c)
            rd = step.finish_round(r, d)
            assert abs(ra["accuracy"] - rb["accuracy"]) <= 1e-5
            assert abs(ra["accuracy"] - rd["accuracy"]) <= 1e-5
            if r == 0:
                assert abs(ra["accuracy"] - rc["accuracy"]) <= 1e-5
            n_sel += int(np.asarray(c["mask"]).sum())
        return n_sel

out = {}
out["dcs_k4"] = run_case("dcs", 4, rounds=2)
out["dcs_k8"] = run_case("dcs", 8, rounds=1)
out["ccs_fuzzy_k4"] = run_case("ccs-fuzzy", 4, rounds=1)
out["ok"] = True
print(json.dumps(out))
"""


def test_fused_sharded_parity_on_forced_meshes():
    """Fused fast path under 4-/8-device client meshes (tight per-shard
    probe regions, psum/pmax seams outside the fused op): masks
    bit-identical to the unfused single-device prefix; N % K != 0 pads
    dummy clients."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=1500)
    assert proc.returncode == 0, \
        f"fused sharded parity child failed:\n{proc.stderr[-4000:]}"
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["ok"]
    assert data["dcs_k4"] > 0 and data["dcs_k8"] > 0


# --------------------------------------------------------------------------
# round-ahead scheduler determinism
# --------------------------------------------------------------------------

def test_overlap_scheduler_matches_serial():
    """The round-ahead driver must be a pure pipelining change: rows
    (accuracy, counts, comm accounting) and per-round masks identical
    to the serial driver."""
    serial = FLSimulation(_cfg("dcs"))
    rows_s, masks_s = [], []
    for r in range(N_ROUNDS):
        rows_s.append(serial.run_round(r))
        masks_s.append(serial.last_mask.copy())

    overlap = FLSimulation(_cfg("dcs"))
    rows_o = overlap.run(N_ROUNDS, overlap=True)
    assert rows_s == rows_o
    np.testing.assert_array_equal(masks_s[-1], overlap.last_mask)


def test_overlap_scheduler_matches_serial_fused():
    """Overlap x fused compose: still bit-identical rows."""
    a = FLSimulation(_cfg("random"))        # fused is the default now
    b = FLSimulation(_cfg("random"))
    assert a.run(N_ROUNDS, overlap=False) == b.run(N_ROUNDS, overlap=True)


def test_sweep_overlap_rows_identical():
    """The sweep's seed-vmapped round-ahead path (donated seed-stacked
    params) reproduces the serial sweep rows exactly."""
    from repro.launch.sweep import run_seed_group

    def tiny_cfg(scheme, classes, dist, seed):
        cfg = _cfg(scheme, seed=seed)
        cfg.mobility = MobilityConfig(n_vehicles=N_CLIENTS,
                                      distribution=dist, seed=seed)
        return cfg

    a = run_seed_group("dcs", 9, "uniform", [0, 1], 2, cfg_fn=tiny_cfg,
                       overlap=False)
    b = run_seed_group("dcs", 9, "uniform", [0, 1], 2, cfg_fn=tiny_cfg,
                       overlap=True)
    assert a == b
