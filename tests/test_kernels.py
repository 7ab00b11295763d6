"""Per-kernel Pallas (interpret=True) vs pure-jnp oracle, with shape/dtype
sweeps, plus fast-path (jnp chunked) vs oracle equivalence."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rules import build_rule_table
from repro.kernels import ref as kref
from repro.kernels.fuzzy_eval import fuzzy_eval_pallas
from repro.kernels.neighbor_elect import neighbor_elect_pallas
from repro.kernels.wkv6 import wkv6_pallas
from repro.models.rwkv6 import wkv6_scan


# --------------------------------------------------------------------------
# WKV6
# --------------------------------------------------------------------------

def _wkv_inputs(b, t, h, n, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r = jax.random.normal(ks[0], (b, t, h, n), dtype)
    k = jax.random.normal(ks[1], (b, t, h, n), dtype)
    v = jax.random.normal(ks[2], (b, t, h, n), dtype)
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.5
         + 0.45).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (h, n)) * 0.1).astype(jnp.float32)
    s0 = (jax.random.normal(ks[5], (b, h, n, n)) * 0.1).astype(jnp.float32)
    return r, k, v, w, u, s0


@pytest.mark.parametrize("b,t,h,n", [(1, 32, 1, 64), (2, 128, 3, 64),
                                     (2, 256, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_pallas_vs_oracle(b, t, h, n, dtype):
    r, k, v, w, u, s0 = _wkv_inputs(b, t, h, n, dtype)
    y0, sT0 = kref.wkv6_ref(r, k, v, w, u, s0)
    y1, sT1 = wkv6_pallas(r, k, v, w, u, s0, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(y0, np.float32),
                               np.asarray(y1, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sT0), np.asarray(sT1),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("t", [64, 256, 512])
def test_wkv6_chunked_scan_vs_oracle(t):
    r, k, v, w, u, s0 = _wkv_inputs(2, t, 2, 64, jnp.float32, seed=3)
    y0, sT0 = kref.wkv6_ref(r, k, v, w, u, s0)
    y1, sT1 = wkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sT0), np.asarray(sT1),
                               atol=1e-4, rtol=1e-4)


def test_wkv6_grad_flows():
    r, k, v, w, u, s0 = _wkv_inputs(1, 64, 1, 64, jnp.float32, seed=4)

    def loss(r_):
        y, _ = wkv6_scan(r_, k, v, w, u, s0)
        return jnp.sum(jnp.square(y))

    g = jax.grad(loss)(r)
    assert not jnp.isnan(g).any()
    assert float(jnp.abs(g).max()) > 0


# --------------------------------------------------------------------------
# fuzzy_eval
# --------------------------------------------------------------------------

@pytest.mark.parametrize("p", [1, 30, 300, 1025])
def test_fuzzy_pallas_vs_oracle(p):
    table, levels = build_rule_table()
    x = jax.random.uniform(jax.random.PRNGKey(p), (p, 4))
    means = jnp.tile(jnp.array([0.15, 0.5, 0.85]), (4, 1))
    sigmas = jnp.full((4, 3), 0.18)
    centers = jnp.linspace(0.0, 100.0, 9)
    e0 = kref.fuzzy_eval_ref(x, means, sigmas, table, levels, centers)
    e1 = fuzzy_eval_pallas(x, means, sigmas, table, levels, centers,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(e0), np.asarray(e1),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("p", [1, 30, 300, 1025])
def test_fuzzy_pallas_vs_jnp_normalize_raw(p):
    """Eq. 8 folded into the kernel (ISSUE 3): pallas-interpret and the
    jnp reference agree on *raw* feature batches (arbitrary per-column
    scales: |D_i| ~ 1e3, TA ~ 1e7, CC ~ 1, LF ~ 1), and the in-kernel
    normalization equals host-side Eq. 8 + the unnormalized kernel."""
    table, levels = build_rule_table()
    scales = jnp.array([4.5e3, 1.04e7, 1.0, 2.3])
    x = jax.random.uniform(jax.random.PRNGKey(p + 7), (p, 4)) * scales
    means = jnp.tile(jnp.array([0.15, 0.5, 0.85]), (4, 1))
    sigmas = jnp.full((4, 3), 0.18)
    centers = jnp.linspace(0.0, 100.0, 9)
    e_jnp = kref.fuzzy_eval_ref(x, means, sigmas, table, levels, centers,
                                normalize=True)
    e_pal = fuzzy_eval_pallas(x, means, sigmas, table, levels, centers,
                              interpret=True, normalize=True)
    np.testing.assert_allclose(np.asarray(e_jnp), np.asarray(e_pal),
                               atol=1e-3, rtol=1e-4)
    # folded == host-side Eq. 8 (value / column max) + plain kernel
    x_norm = x / jnp.maximum(x.max(axis=0), 1e-9)
    e_host = kref.fuzzy_eval_ref(x_norm, means, sigmas, table, levels,
                                 centers)
    np.testing.assert_allclose(np.asarray(e_jnp), np.asarray(e_host),
                               atol=1e-4, rtol=1e-5)


# --------------------------------------------------------------------------
# neighbor_elect
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,rng,top_m", [(30, 200.0, 2), (300, 200.0, 2),
                                         (1000, 150.0, 3), (257, 50.0, 1)])
def test_elect_pallas_vs_oracle(n, rng, top_m):
    pos = jax.random.uniform(jax.random.PRNGKey(n), (n,)) * 1000.0
    ev = jax.random.uniform(jax.random.PRNGKey(n + 1), (n,)) * 100.0
    s0 = kref.neighbor_elect_ref(pos, ev, comm_range=rng, top_m=top_m,
                                 e_tau=30.0)
    s1 = neighbor_elect_pallas(pos, ev, comm_range=rng, top_m=top_m,
                               e_tau=30.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_elect_topm_bound_per_neighbourhood():
    """In any ``comm_range`` window at most top_m + boundary effects are
    selected; with all vehicles in one point, exactly top_m."""
    n, top_m = 50, 2
    pos = jnp.zeros((n,))
    ev = jnp.arange(n, dtype=jnp.float32)
    sel = kref.neighbor_elect_ref(pos, ev, comm_range=200.0, top_m=top_m,
                                  e_tau=0.0)
    assert int(sel.sum()) == top_m
    # the selected ones are the best evaluations
    assert set(np.where(np.asarray(sel))[0]) == {n - 1, n - 2}


def test_elect_threshold():
    pos = jnp.linspace(0, 1000, 10)
    ev = jnp.full((10,), 10.0)
    sel = kref.neighbor_elect_ref(pos, ev, comm_range=200.0, top_m=2,
                                  e_tau=30.0)
    assert int(sel.sum()) == 0        # nobody clears E_tau


# --------------------------------------------------------------------------
# selective_scan (mamba)
# --------------------------------------------------------------------------

from repro.kernels.selective_scan import selective_scan_pallas


@pytest.mark.parametrize("b,t,di,n", [(1, 64, 256, 16), (2, 128, 256, 16),
                                      (2, 96, 512, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_pallas_vs_oracle(b, t, di, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    x = jax.random.normal(ks[0], (b, t, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, di))
                         - 4.0).astype(dtype)
    bmat = jax.random.normal(ks[2], (b, t, n), dtype)
    cmat = jax.random.normal(ks[3], (b, t, n), dtype)
    a = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.5)
    h0 = (jax.random.normal(ks[5], (b, di, n)) * 0.1).astype(jnp.float32)
    y0, h0T = kref.selective_scan_ref(x, dt, bmat, cmat, a, h0)
    y1, h1T = selective_scan_pallas(x, dt, bmat, cmat, a, h0,
                                    interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h0T), np.asarray(h1T),
                               atol=tol, rtol=tol)


def test_selective_scan_matches_mamba_layer_math():
    """The kernel oracle agrees with the model-side chunked scan
    (models/mamba.py::_ssm_scan)."""
    from repro.models.mamba import _ssm_scan
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    b, t, di, n = 2, 64, 128, 16
    x = jax.random.normal(ks[0], (b, t, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, di)) - 4.0)
    bmat = jax.random.normal(ks[2], (b, t, n))
    cmat = jax.random.normal(ks[3], (b, t, n))
    a = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.5)
    h0 = jnp.zeros((b, di, n))
    y0, hT0 = kref.selective_scan_ref(x, dt, bmat, cmat, a, h0)
    y1, hT1 = _ssm_scan(x, dt, bmat, cmat, a, h0)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(hT0), np.asarray(hT1),
                               atol=2e-4, rtol=2e-4)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

from repro.kernels.flash_attention import flash_attention_pallas
from repro.models.attention import flash_attention as _flash_jnp


@pytest.mark.parametrize("sq,skv,hq,hkv,dh,causal,window,prefix", [
    (128, 128, 4, 2, 32, True, 0, 0),       # GQA causal
    (256, 256, 4, 1, 64, True, 64, 0),      # MQA sliding window
    (128, 128, 2, 2, 32, True, 0, 32),      # prefix-LM
    (96, 160, 4, 4, 32, False, 0, 0),       # cross-attn, irregular sizes
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_vs_jnp(sq, skv, hq, hkv, dh, causal, window, prefix,
                             dtype):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (2, sq, hq, dh), dtype)
    k = jax.random.normal(ks[1], (2, skv, hkv, dh), dtype)
    v = jax.random.normal(ks[2], (2, skv, hkv, dh), dtype)
    out_p = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                   prefix_len=prefix, interpret=True)
    out_j = _flash_jnp(q, k, v, jnp.arange(sq), jnp.arange(skv),
                       causal=causal, window=window, prefix_len=prefix,
                       q_chunk=64, kv_chunk=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_p, np.float32),
                               np.asarray(out_j, np.float32),
                               atol=tol, rtol=tol)


def test_pallas_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is the CPU's; the TPU compiles; any other backend
    refuses the Pallas impl instead of silently interpreting."""
    from repro.kernels import ops
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.pallas_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="backend 'gpu'"):
        ops.pallas_interpret()
