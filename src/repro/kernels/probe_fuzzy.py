"""Pallas TPU kernel: fused Eq. 7 probe -> Eq. 8 -> Mamdani evaluation.

The per-round selection hot path runs the probe CNN forward over every
participant's probe samples, normalizes the four objective columns and
evaluates the 81-rule Mamdani base — previously three dispatches
(``dataset_loss_packed`` -> transpose/stack -> ``fuzzy_eval_pallas``)
with the packed activations round-tripping through HBM between them.
This kernel fuses the chain into ONE launch:

- grid over blocks of ``block_s`` packed probe samples (TPU grid order
  is sequential, so the per-client loss accumulator lives in VMEM
  scratch and carries across blocks);
- per block: conv1 -> pool -> conv2 -> pool -> fc1 -> fc2 in VMEM, laid
  out image-row-major: a ``(rows, block_s, lanes)`` block holds one
  ``(block_s, lanes)`` slab per image row, with (column, channel) on the
  lanes.  A SAME convolution is then, per output row, a sum over the
  ``k`` kernel rows of slab @ band matrix (``_band``: the k column taps
  and the input channels folded into one banded weight, built once per
  call outside the kernel).  Image-row shifts are slab indices and the
  zero padding is a skipped tap, so no pad, slice or reshape of an
  activation happens in the kernel (Mosaic has no conv primitive and
  refuses 4-D shape casts).  The 2x2 max pool is folded in: the band
  emits even and odd output columns into two lane-aligned halves, and
  the pool takes the max over two rows x two halves before bias + ReLU
  (both monotone, so the order is exact).  fc1 sums the pooled row slabs
  against the matching row blocks of its weight, which is the NHWC
  flatten;
- the per-sample NLL reduces into per-client lanes through a one-hot
  mask summed over the sample (sublane) axis — a scatter would
  serialize;
- the last grid step divides by the per-client counts (Eq. 7 mean),
  assembles the (4, lanes) raw feature block, applies Eq. 8 max-scaling
  (external column maxima — the mesh-sharded path's pmax seam — or
  in-kernel masked lane maxima) and runs the shared ``mamdani_lanes``
  inference from ``kernels/fuzzy_eval.py``.

Clients live on the lane axis (``n_clients + 1`` lanes rounded up to a
lane multiple; the ``+ 1`` overflow lane swallows padding samples).
Labels and client ids enter as ``(block_s, 1)`` columns, so a block only
has to be a sublane multiple.  VMEM framing (paper CNN, fp32): the
conv2 band (5 x 512 x 1024 = 10.5 MB) and the row-blocked fc1 weight
(7 x 512 x 512 = 7.3 MB) dominate; every weight block is constant across
the grid and single-buffered, and ``VMEM_LIMIT`` raises the scoped
limit above the 16 MiB default to hold them.  ``block_s = 64`` keeps the
activation slabs near 2 MB.

On the TPU the kernel compiles for the chip; on the CPU it executes in
interpret mode (parity tests).  The FL path runs it only under
``REPRO_KERNEL_IMPL=pallas`` — the default impl everywhere is the jnp
path in ``kernels/ops.py``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fuzzy_eval import (LANE, NUM_LEVELS, NUM_OUT, NUM_VARS,
                                      mamdani_lanes, static_rules)

BLOCK_S = 64         # probe samples per grid step (see VMEM framing above)
VMEM_LIMIT = 64 * 1024 * 1024    # v5e has 128 MiB of VMEM per core


def _round_lanes(n: int) -> int:
    return -(-n // LANE) * LANE


def _band(w: jax.Array, width: int, lanes_in: int) -> jax.Array:
    """One SAME stride-1 convolution row as ``k`` banded matmuls.

    ``w`` (k, k, cin, cout) HWIO -> (k, lanes_in, 2 * half) where
    ``half`` is ``width // 2 * cout`` rounded up to a lane multiple: for
    kernel row ``dy``, input lane ``x' * cin + ci`` feeds output lane
    ``p * half + (x // 2) * cout + co`` with ``p = x % 2``, weighted
    ``w[dy, x' - x + k // 2, ci, co]`` inside the band and 0 outside
    (which is the SAME zero padding along the row).  Built by gather +
    select, so every entry is an exact copy of a weight or 0."""
    k, _, cin, cout = w.shape
    xs = jnp.arange(width)
    dx = xs[:, None] - xs[None, :] + k // 2            # (x', x)
    inside = (dx >= 0) & (dx < k)
    taps = jnp.where(inside[None, :, :, None, None],
                     w[:, jnp.clip(dx, 0, k - 1)], 0.0)  # (k, x', x, ci, co)
    taps = taps.transpose(0, 1, 3, 2, 4)                 # (k, x', ci, x, co)
    half = _round_lanes(width // 2 * cout)
    halves = []
    for parity in (0, 1):
        t = taps[:, :, :, parity::2, :].reshape(k, width * cin, -1)
        halves.append(jnp.pad(t, ((0, 0), (0, lanes_in - width * cin),
                                  (0, half - t.shape[-1]))))
    return jnp.concatenate(halves, axis=2)


def _conv_pool(rows, band_ref, b_ref):
    """SAME conv + bias + ReLU + 2x2 max pool over image-row slabs:
    ``rows`` is a list of (bs, lanes_in) slabs, one per input row;
    returns the pooled (bs, half) slabs, one per pair of rows."""
    k = band_ref.shape[0]
    half = b_ref.shape[1]
    n = len(rows)
    pooled = []
    for i in range(n // 2):
        best = None
        for y in (2 * i, 2 * i + 1):
            acc = None
            for dy in range(k):
                src = y + dy - k // 2
                if 0 <= src < n:                     # else: zero padding
                    term = jnp.dot(rows[src], band_ref[dy],
                                   preferred_element_type=jnp.float32)
                    acc = term if acc is None else acc + term
            for part in (acc[:, :half], acc[:, half:]):
                best = part if best is None else jnp.maximum(best, part)
        pooled.append(jnp.maximum(best + b_ref[...], 0.0))
    return pooled


def _block_losses(im_ref, lb_ref, t1_ref, b1_ref, t2_ref, b2_ref,
                  f1_ref, fb1_ref, f2_ref, fb2_ref) -> jax.Array:
    """One block's CNN forward + per-sample NLL: (block_s, 1) losses."""
    x = [im_ref[r] for r in range(im_ref.shape[0])]  # (bs, img) per row
    x = _conv_pool(x, t1_ref, b1_ref)
    x = _conv_pool(x, t2_ref, b2_ref)
    h = fb1_ref[...]
    for r, slab in enumerate(x):                     # NHWC flatten @ fc1
        h = h + jnp.dot(slab, f1_ref[r], preferred_element_type=jnp.float32)
    h = jnp.maximum(h, 0.0)
    logits = jnp.dot(h, f2_ref[...],
                     preferred_element_type=jnp.float32) + fb2_ref[...]
    zmax = jnp.max(logits, axis=-1, keepdims=True)
    logz = zmax + jnp.log(jnp.sum(jnp.exp(logits - zmax), axis=-1,
                                  keepdims=True))
    n_cls = logits.shape[-1]
    onehot = (lb_ref[...] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, n_cls), 1)
              ).astype(logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1, keepdims=True)
    return logz - gold                               # (bs, 1)


def _accumulate(acc_ref, losses: jax.Array, seg_ref, lanes: int) -> None:
    """Per-client one-hot loss reduction on the lane axis."""
    onehot = (seg_ref[...] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
              ).astype(jnp.float32)                  # (bs, lanes)
    acc_ref[...] += jnp.sum(losses * onehot, axis=0, keepdims=True)


def _fused_kernel(im_ref, lb_ref, seg_ref, counts_ref, aux_ref, means_ref,
                  sigmas_ref, centers_ref, colmax_ref, t1_ref, b1_ref,
                  t2_ref, b2_ref, f1_ref, fb1_ref, f2_ref, fb2_ref,
                  lf_ref, ev_ref, acc_ref, *, rule_table: tuple,
                  rule_levels: tuple, n_clients: int,
                  external_maxima: bool):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lf_ref[...] = jnp.zeros_like(lf_ref)
        ev_ref[...] = jnp.zeros_like(ev_ref)

    losses = _block_losses(im_ref, lb_ref, t1_ref, b1_ref, t2_ref, b2_ref,
                           f1_ref, fb1_ref, f2_ref, fb2_ref)
    lanes = acc_ref.shape[1]
    _accumulate(acc_ref, losses, seg_ref, lanes)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        lf = acc_ref[...] / jnp.maximum(counts_ref[...], 1.0)   # (1, lanes)
        lf_ref[...] = lf
        # aux rows 0-2 hold [SQ, TA, CC]; row 3 (zeros) takes the loss
        row = jax.lax.broadcasted_iota(jnp.int32, (NUM_VARS, lanes), 0)
        feats = jnp.where(row == NUM_VARS - 1, lf, aux_ref[...])
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
                 < n_clients)                        # (1, lanes)
        if external_maxima:
            maxima = colmax_ref[...]                 # (V, 1)
        else:                                        # Eq. 8 over the fleet
            maxima = jnp.max(jnp.where(valid, feats, -jnp.inf),
                             axis=1, keepdims=True)
        x = jnp.clip(feats / jnp.maximum(maxima, 1e-9), 0.0, 1.0)
        ev = mamdani_lanes(x, means_ref[...], sigmas_ref[...],
                           centers_ref[...], rule_table, rule_levels)
        ev_ref[...] = jnp.where(valid, ev[None, :], 0.0)


def _loss_kernel(im_ref, lb_ref, seg_ref, counts_ref, t1_ref, b1_ref,
                 t2_ref, b2_ref, f1_ref, fb1_ref, f2_ref, fb2_ref,
                 lf_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lf_ref[...] = jnp.zeros_like(lf_ref)

    losses = _block_losses(im_ref, lb_ref, t1_ref, b1_ref, t2_ref, b2_ref,
                           f1_ref, fb1_ref, f2_ref, fb2_ref)
    _accumulate(acc_ref, losses, seg_ref, acc_ref.shape[1])

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        lf_ref[...] = (acc_ref[...] /
                       jnp.maximum(counts_ref[...], 1.0))


def _lanes(n_clients: int) -> int:
    """Client lanes: n + 1 (overflow lane for padding samples) rounded
    up to a lane multiple."""
    return _round_lanes(n_clients + 1)


def _cnn_weights(params, width: int):
    """The CNN in kernel layout for ``width``-pixel images: conv bands
    (``_band``) with biases tiled over the pooled columns and
    lane-padded, fc1 split into one row block per pooled image row (rows
    of the pad lanes are zero), fc2 as is."""
    f32 = jnp.float32
    w1 = params["conv1"]["w"].astype(f32)
    t1 = _band(w1, width, width * w1.shape[2])
    t2 = _band(params["conv2"]["w"].astype(f32), width // 2,
               t1.shape[-1] // 2)

    def bias(b, cols, lanes):
        b = jnp.tile(b.astype(f32), cols)
        return jnp.pad(b, (0, lanes - b.shape[0]))[None, :]

    rows = width // 4
    half2 = t2.shape[-1] // 2
    fc1 = params["fc1"]["w"].astype(f32)
    f1 = fc1.reshape(rows, -1, fc1.shape[1])        # (row, col * c2, out)
    return [t1, bias(params["conv1"]["b"], width // 2, t1.shape[-1] // 2),
            t2, bias(params["conv2"]["b"], rows, half2),
            jnp.pad(f1, ((0, 0), (0, half2 - f1.shape[1]), (0, 0))),
            params["fc1"]["b"].astype(f32)[None, :],
            params["fc2"]["w"].astype(f32),
            params["fc2"]["b"].astype(f32)[None, :]]


def _packed_operands(params, images, labels, seg, counts, n_clients: int,
                     block_s: int):
    """Pad the packed probe to whole blocks and lay it out for the
    kernel: images as (rows, S, cols) image-row slabs, labels and client
    ids as (S, 1) columns, counts on the client lanes."""
    s = images.shape[0]
    pad = (-s) % block_s
    f32 = jnp.float32
    im = images.reshape(s, images.shape[1], images.shape[2]).astype(f32)
    if pad:
        im = jnp.pad(im, ((0, pad), (0, 0), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        seg = jnp.pad(seg, (0, pad), constant_values=n_clients)
    lanes = _lanes(n_clients)
    counts_l = jnp.zeros((1, lanes), f32).at[0, :n_clients].set(
        counts.astype(f32))
    return (im.transpose(1, 0, 2), labels.astype(jnp.int32)[:, None],
            seg.astype(jnp.int32)[:, None], counts_l,
            _cnn_weights(params, im.shape[2]), lanes, im.shape[0] // block_s)


def _rep(shape):
    """A block that is the whole array at every grid step: fetched once,
    so one buffer is enough."""
    return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape),
                        pipeline_mode=pl.Buffered(1))


def _sample_specs(im, block_s: int):
    """The per-step blocks: ``block_s`` samples of every image row, and
    their labels and client ids."""
    rows, _, cols = im.shape
    return [pl.BlockSpec((rows, block_s, cols), lambda i: (0, i, 0)),
            pl.BlockSpec((block_s, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_s, 1), lambda i: (i, 0))]


_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=VMEM_LIMIT)


def probe_loss_pallas(params, images: jax.Array, labels: jax.Array,
                      seg: jax.Array, counts: jax.Array, *, n_clients: int,
                      block_s: int = BLOCK_S,
                      interpret: bool = True) -> jax.Array:
    """Eq. 7 packed probe as one kernel launch: (S, 28, 28, 1) samples ->
    (N,) per-client mean losses.  The mesh-sharded prefix calls this per
    shard and psums the result (its collective seam stays outside the
    kernel)."""
    im, lb, sg, counts_l, weights, lanes, nb = _packed_operands(
        params, images, labels, seg, counts, n_clients, block_s)
    out = pl.pallas_call(
        _loss_kernel,
        grid=(nb,),
        in_specs=_sample_specs(im, block_s) + [_rep((1, lanes))]
        + [_rep(tuple(w.shape)) for w in weights],
        out_specs=_rep((1, lanes)),
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(im, lb, sg, counts_l, *weights)
    return out[0, :n_clients]


def probe_fuzzy_pallas(params, images: jax.Array, labels: jax.Array,
                       seg: jax.Array, counts: jax.Array, aux: jax.Array,
                       means: jax.Array, sigmas: jax.Array,
                       rule_table: np.ndarray, rule_levels: np.ndarray,
                       level_centers: jax.Array, *, n_clients: int,
                       block_s: int = BLOCK_S, interpret: bool = True,
                       col_maxima: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """The fused fast path: packed probe samples in, per-client raw
    features and Mamdani evaluations out, one launch.

    aux: (N, 3) raw [SQ, TA, CC] columns (LF comes from the probe);
    col_maxima: optional (4,) external Eq. 8 maxima.  Returns
    ``(feats (N, 4), evals (N,))``."""
    im, lb, sg, counts_l, weights, lanes, nb = _packed_operands(
        params, images, labels, seg, counts, n_clients, block_s)
    f32 = jnp.float32
    aux_l = jnp.zeros((NUM_VARS, lanes), f32).at[:3, :n_clients].set(
        aux.T.astype(f32))
    external = col_maxima is not None
    colmax = (col_maxima.astype(f32)[:, None] if external
              else jnp.ones((NUM_VARS, 1), f32))
    table, levels = static_rules(rule_table, rule_levels)

    lf, ev = pl.pallas_call(
        functools.partial(_fused_kernel, rule_table=table,
                          rule_levels=levels, n_clients=n_clients,
                          external_maxima=external),
        grid=(nb,),
        in_specs=_sample_specs(im, block_s) + [
            _rep((1, lanes)),
            _rep((NUM_VARS, lanes)),
            _rep((NUM_VARS, NUM_LEVELS)),
            _rep((NUM_VARS, NUM_LEVELS)),
            _rep((1, NUM_OUT)),
            _rep((NUM_VARS, 1)),
        ] + [_rep(tuple(w.shape)) for w in weights],
        out_specs=[_rep((1, lanes)), _rep((1, lanes))],
        out_shape=[jax.ShapeDtypeStruct((1, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((1, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(im, lb, sg, counts_l, aux_l, means.astype(f32), sigmas.astype(f32),
      level_centers.astype(f32)[None, :], colmax, *weights)
    lf_n = lf[0, :n_clients]
    feats = jnp.concatenate([aux.astype(f32), lf_n[:, None]], axis=1)
    return feats, ev[0, :n_clients]
