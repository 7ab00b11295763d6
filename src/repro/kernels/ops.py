"""Jit'd dispatch wrappers over the Pallas kernels and their jnp paths.

Selection order (env ``REPRO_KERNEL_IMPL`` or the ``impl=`` argument):
- ``jnp``     : pure-jnp implementation, the default on every backend
                (the TPU included); identical math to the oracle,
                chunked/vmapped, compiled by XLA.
- ``pallas``  : the Pallas kernel — compiled for the chip on a TPU,
                interpret mode on the CPU, refused on any other backend
                (``pallas_interpret``).
- ``oracle``  : the naive reference from ``ref.py`` (tests only).

The env var is read while a caller traces, and jit caches are not keyed
on it: a process that changes ``REPRO_KERNEL_IMPL`` after its first
round must ``jax.clear_caches()`` before the new impl takes effect.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref


def _impl(arg: Optional[str]) -> str:
    return arg or os.environ.get("REPRO_KERNEL_IMPL", "jnp")


def pallas_interpret() -> bool:
    """The ``interpret=`` flag of every Pallas call: the kernels compile
    for the chip on a TPU and run in interpret mode on the CPU (tests).
    Any other backend raises rather than silently interpreting."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels target the TPU (interpret mode on the CPU); "
        f"backend {backend!r} has neither — use the jnp impl")


# --------------------------------------------------------------------------
# WKV6
# --------------------------------------------------------------------------

def wkv6(r, k, v, w, u, s0, impl: Optional[str] = None
         ) -> Tuple[jax.Array, jax.Array]:
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.wkv6 import wkv6_pallas
        return wkv6_pallas(r, k, v, w, u, s0, interpret=pallas_interpret())
    if m == "oracle":
        return kref.wkv6_ref(r, k, v, w, u, s0)
    if m == "scan":
        from repro.models.rwkv6 import wkv6_scan   # per-step (paper-naive)
        return wkv6_scan(r, k, v, w, u, s0)
    # default: chunked matmul formulation (TPU-native; see rwkv6.py)
    from repro.models.rwkv6 import wkv6_chunked
    return wkv6_chunked(r, k, v, w, u, s0)


# --------------------------------------------------------------------------
# Fuzzy evaluation
# --------------------------------------------------------------------------

def fuzzy_eval(x, means, sigmas, rule_table: np.ndarray,
               rule_levels: np.ndarray, level_centers,
               impl: Optional[str] = None,
               normalize: bool = False,
               col_maxima=None) -> jax.Array:
    """``normalize=True`` accepts raw feature columns and applies Eq. 8
    per-column max-scaling inside the kernel (both impls) — the staged
    ``evaluate`` stage feeds raw [SQ, TA, CC, LF].

    ``col_maxima`` (only meaningful with ``normalize=True``) supplies the
    per-column maxima externally instead of computing them over ``x`` —
    the mesh-sharded prefix pmax-reduces the maxima across client
    shards and passes them here, so each shard normalizes against the
    *global* Eq. 8 denominator.  The scaling ops match the jnp/ref
    in-kernel path exactly (``x / maxima``), so results are bitwise-equal
    to it when ``col_maxima`` equals ``x.max(axis=0)`` of the full
    batch; the Pallas kernel normalizes via a reciprocal multiply and
    may differ in the last ulp."""
    if normalize and col_maxima is not None:
        maxima = jnp.maximum(col_maxima, 1e-9)
        x = jnp.clip(x / maxima, 0.0, 1.0)
        normalize = False
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.fuzzy_eval import fuzzy_eval_pallas
        return fuzzy_eval_pallas(x, means, sigmas, rule_table, rule_levels,
                                 level_centers, interpret=pallas_interpret(),
                                 normalize=normalize)
    return kref.fuzzy_eval_ref(x, means, sigmas, rule_table, rule_levels,
                               level_centers, normalize=normalize)


# --------------------------------------------------------------------------
# Fused Eq. 7 probe -> Eq. 8 -> Mamdani evaluation (the selection hot path)
# --------------------------------------------------------------------------

def probe_fuzzy(params, images, labels, seg, counts, aux, means, sigmas,
                rule_table: np.ndarray, rule_levels: np.ndarray,
                level_centers, *, n_clients: int, batch: int = 128,
                impl: Optional[str] = None,
                col_maxima=None) -> Tuple[jax.Array, jax.Array]:
    """The selection prefix's device-resident fast path: packed Eq. 7
    probe samples -> per-client raw features + Mamdani evaluations.

    - ``jnp`` (default): the chunked packed probe
      (``dataset_loss_packed``) and the reference Mamdani inference fused
      into the caller's jit — one XLA program, no intermediate host or
      HBM round-trips between the stages.
    - ``pallas``: ONE kernel launch (``probe_fuzzy_pallas``): the conv/
      pool/dense probe staged through VMEM, per-client one-hot loss
      reduction on the lane axis, Eq. 8 + 81-rule Mamdani on the final
      grid step.  Interpret mode on the CPU.
    - ``oracle``: the naive unchunked transcription (tests only).

    ``aux``: (N, 3) raw [SQ, TA, CC] columns; ``col_maxima``: optional
    (4,) external Eq. 8 maxima (the mesh-sharded prefix's pmax seam).
    Returns ``(feats (N, 4) raw, evals (N,))``."""
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.probe_fuzzy import probe_fuzzy_pallas
        return probe_fuzzy_pallas(params, images, labels, seg, counts, aux,
                                  means, sigmas, rule_table, rule_levels,
                                  level_centers, n_clients=n_clients,
                                  interpret=pallas_interpret(),
                                  col_maxima=col_maxima)
    if m == "oracle":
        return kref.probe_fuzzy_ref(params, images, labels, seg, counts,
                                    aux, means, sigmas, rule_table,
                                    rule_levels, level_centers,
                                    n_clients=n_clients,
                                    col_maxima=col_maxima)
    from repro.fl.client import dataset_loss_packed
    lf = dataset_loss_packed(params, images, labels, seg, counts,
                             n_clients=n_clients, batch=batch)
    feats = jnp.concatenate([aux, lf[:, None]], axis=1).astype(jnp.float32)
    evals = fuzzy_eval(feats, means, sigmas, rule_table, rule_levels,
                       level_centers, impl="jnp", normalize=True,
                       col_maxima=col_maxima)
    return feats, evals


def probe_loss(params, images, labels, seg, counts, *, n_clients: int,
               batch: int = 128, impl: Optional[str] = None) -> jax.Array:
    """The fused fast path's probe half alone: (N,) per-client Eq. 7 mean
    losses.  The mesh-sharded prefix runs this per shard — the psum that
    merges shards' loss lanes stays outside the kernel."""
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.probe_fuzzy import probe_loss_pallas
        return probe_loss_pallas(params, images, labels, seg, counts,
                                 n_clients=n_clients,
                                 interpret=pallas_interpret())
    if m == "oracle":
        return kref.probe_loss_ref(params, images, labels, seg, counts,
                                   n_clients=n_clients)
    from repro.fl.client import dataset_loss_packed
    return dataset_loss_packed(params, images, labels, seg, counts,
                               n_clients=n_clients, batch=batch)


# --------------------------------------------------------------------------
# Neighbour election
# --------------------------------------------------------------------------

def neighbor_elect(pos, evals, *, comm_range: float, top_m: int,
                   e_tau: float, impl: Optional[str] = None) -> jax.Array:
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.neighbor_elect import neighbor_elect_pallas
        return neighbor_elect_pallas(pos, evals, comm_range=comm_range,
                                     top_m=top_m, e_tau=e_tau,
                                     interpret=pallas_interpret())
    return kref.neighbor_elect_ref(pos, evals, comm_range=comm_range,
                                   top_m=top_m, e_tau=e_tau)


def neighbor_elect_windowed(pos, evals, *, comm_range: float, top_m: int,
                            e_tau: float, window: int,
                            impl: Optional[str] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """O(N*W) windowed election -> ``(mask (N,) int32, overflow ()
    int32)``.  ``overflow == 0`` certifies the mask bit-identical to
    ``neighbor_elect``; callers re-run the dense election otherwise.
    ``pallas`` routes the sorted counting sweep through
    ``windowed_counts_pallas``; ``oracle`` is the naive ref (dense mask +
    rank-distance overflow check, tests only)."""
    m = _impl(impl)
    if m == "oracle":
        return kref.windowed_elect_ref(pos, evals, comm_range=comm_range,
                                       top_m=top_m, e_tau=e_tau,
                                       window=window)
    from repro.core.elect import windowed_elect
    return windowed_elect(pos, evals, comm_range=comm_range, top_m=top_m,
                          e_tau=e_tau, window=window,
                          impl="pallas" if m == "pallas" else "jnp")


# --------------------------------------------------------------------------
# Selective scan (Mamba-1)
# --------------------------------------------------------------------------

def selective_scan(x, dt, bmat, cmat, a, h0, impl: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.selective_scan import selective_scan_pallas
        return selective_scan_pallas(x, dt, bmat, cmat, a, h0,
                                     interpret=pallas_interpret())
    return kref.selective_scan_ref(x, dt, bmat, cmat, a, h0)


# --------------------------------------------------------------------------
# Flash attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, q_pos, kv_pos, *, causal=True, window=0,
                    prefix_len=0, impl: Optional[str] = None) -> jax.Array:
    """Self-attention layout (q_pos/kv_pos = arange).  The Pallas path is
    the real TPU kernel; the jnp path is the GSPMD-friendly chunked
    softmax in models/attention.py."""
    m = _impl(impl)
    if m == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      prefix_len=prefix_len,
                                      interpret=pallas_interpret())
    from repro.models.attention import flash_attention as flash_jnp
    return flash_jnp(q, k, v, q_pos, kv_pos, causal=causal, window=window,
                     prefix_len=prefix_len)
