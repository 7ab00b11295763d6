"""Windowed DCS neighbour election (ISSUE 9 tentpole).

The paper's Alg. 1 only ever compares a vehicle against neighbours
within ``comm_range``, but the reference election
(``kernels/ref.py::neighbor_elect_ref``) — and the sharded prefix's
full-``(N,)`` ``all_gather`` seam built on it — pay O(N^2) compares and
O(N) collective bytes regardless of how local the physics is.  This
module exploits the locality: **sorted by road position, the in-range
neighbours of any vehicle form a contiguous index run** (distance is
linear ``|x_i - x_j|``), so a window of ``W`` sorted neighbours per side
covers every comparison that can matter, and the per-vehicle cost drops
to O(W).

Everything here is *exact or flagged*: the counting compares are the
bitwise-identical ``(d <= comm_range)`` / eval / index-tie predicates of
the reference on the same float values, and whenever a fixed window or
buffer capacity could have truncated a comparison that the reference
would make, a runtime ``overflow`` flag is raised instead of silently
diverging.  Callers (the staged prefix drivers) re-run the affected
round through the gather election on overflow — so the windowed masks
are bit-identical to the full election whenever they are used at all.

Three layers share the core:

- ``windowed_elect``      — single-device: sort, blocked window counts,
  scatter back (the O(N*W) replacement for the O(N^2) kernel sweep);
- ``ring_halo_elect``     — inside ``shard_map``: re-bucket clients into
  road-segment shards with one tiled ``all_to_all``, exchange fixed-
  width boundary halos with the ``h = ceil(comm_range / segment)``
  adjacent shards over a ``ppermute`` ring (wrap-around ring topology;
  the wrapped strips are masked empty because road distance is linear),
  elect on local+halo candidates, route the masks back through the
  inverse ``all_to_all``.  Per-device compare cost O(N/K * W); the halo
  exchange itself is O(h * W) bytes — flat in N at fixed ``comm_range``
  and density (the O(N/K) re-bucketing shuffle is layout movement, not
  election traffic, and shrinks with the mesh);
- ``sharded_topk_mask``   — the CCS quota on a hierarchical top-k
  (local top-k, gather K*k candidates, global top-k) instead of the
  gathered (N,) vector; exact including the lowest-index tie-break.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# far-away / below-threshold sentinels for padded slots (match the
# Pallas dense kernel's padding convention)
SENT_POS = 1e18
SENT_EV = -1e18


def auto_window(n: int, comm_range: float, road_length: float) -> int:
    """Default sorted-neighbour window: 3x the expected one-side
    in-range population (uniform density) plus slack, clamped to the
    fleet.  Generous on purpose — an undersized window only costs a
    gather fallback, an oversized one only compares more zeros."""
    density = n / max(road_length, 1e-9)
    w = int(3.0 * comm_range * density) + 16
    return max(16, min(n, w))


def auto_capacity(shard_n: int, n_shards: int) -> int:
    """Per-(source shard -> road segment) bucket capacity: 2x the
    uniform expectation plus slack.  Clustered fleets can exceed it —
    that raises the overflow flag, never a wrong mask."""
    return min(shard_n, 2 * (-(-shard_n // n_shards)) + 16)


def _counts_block_jnp(sp: jax.Array, se: jax.Array, sg: jax.Array, *,
                      comm_range: float, e_tau: float, n_valid: int,
                      window: int, block: int) -> jax.Array:
    """Blocked better-neighbour counts over sorted arrays (lax.map over
    row blocks keeps the live compare tile at (block, block + 2W))."""
    m = sp.shape[0]
    nb = m // block
    rel = jnp.arange(-window, block + window)

    def one_block(ib):
        rows = ib * block + jnp.arange(block)
        cand = ib * block + rel
        inb = (cand >= 0) & (cand < m)
        cc = jnp.clip(cand, 0, m - 1)
        cp = jnp.where(inb, sp[cc], SENT_POS)
        ce = jnp.where(inb, se[cc], SENT_EV)
        cg = jnp.where(inb, sg[cc], n_valid)
        pi, ei, gi = sp[rows], se[rows], sg[rows]
        d = jnp.abs(pi[:, None] - cp[None, :])
        ok = (d <= comm_range) & (ce[None, :] >= e_tau) \
            & (cg[None, :] < n_valid)
        better = (ce[None, :] > ei[:, None]) | (
            (ce[None, :] == ei[:, None]) & (cg[None, :] < gi[:, None]))
        return jnp.sum((ok & better).astype(jnp.int32), axis=1)

    return jax.lax.map(one_block, jnp.arange(nb)).reshape(m)


def window_coverage(sp: jax.Array, se: jax.Array, sg: jax.Array, *,
                    comm_range: float, e_tau: float, n_valid: int,
                    window: int, need: jax.Array) -> jax.Array:
    """True iff every ``need`` entry's valid in-range neighbours all lie
    within ``window`` sorted slots — i.e. the windowed counts equal the
    full reference counts.  The range bound widens by a float-safety
    margin (position-scaled), so boundary rounding can only *over*-flag
    (a spurious gather fallback), never under-flag (a wrong mask)."""
    m = sp.shape[0]
    if window >= m - 1:
        return jnp.bool_(True)
    real = sg < n_valid
    span = jnp.max(jnp.where(real, jnp.abs(sp), 0.0))
    cr = comm_range + 1e-5 * jnp.maximum(span, 1.0) + 1e-8
    valid = (real & (se >= e_tau)).astype(jnp.int32)
    cum = jnp.cumsum(valid)

    def count_in(a, b):                       # valid entries in [a, b]
        a = jnp.clip(a, 0, m - 1)
        bc = jnp.clip(b, 0, m - 1)
        c = cum[bc] - jnp.where(a > 0, cum[a - 1], 0)
        return jnp.where(b >= a, c, 0)

    idx = jnp.arange(m)
    lo = jnp.searchsorted(sp, sp - cr, side="left")
    hi = jnp.searchsorted(sp, sp + cr, side="right") - 1
    beyond = count_in(lo, idx - window - 1) + count_in(idx + window + 1, hi)
    return ~jnp.any((beyond > 0) & need)


def sorted_window_counts(sp: jax.Array, se: jax.Array, sg: jax.Array, *,
                         comm_range: float, e_tau: float, n_valid: int,
                         window: int, need: Optional[jax.Array] = None,
                         block: int = 128, impl: str = "jnp"
                         ) -> Tuple[jax.Array, jax.Array]:
    """Better-neighbour counts on a position-sorted candidate array.

    ``sp``/``se``/``sg``: (M,) sorted positions / evals / global ids
    (sentinel slots carry pos=``SENT_POS``, ev=``SENT_EV``, id >=
    ``n_valid``).  Returns ``(counts (M,) int32, covered () bool)``:
    ``counts[i]`` applies the reference predicates against the loaded
    window around ``i``; ``covered`` certifies the window saw every
    comparison the full reference would make for the ``need`` entries
    (default: all real entries).  When ``covered`` the counts — and any
    mask derived from them — are bit-identical to the dense reference."""
    m = sp.shape[0]
    w = min(int(window), m)
    b = min(block, max(32, m))
    mp = -(-m // b) * b
    pad = mp - m
    spp = jnp.pad(sp, (0, pad), constant_values=SENT_POS)
    sep = jnp.pad(se, (0, pad), constant_values=SENT_EV)
    sgp = jnp.pad(sg, (0, pad), constant_values=n_valid)
    if impl == "pallas":
        from repro.kernels.neighbor_elect import windowed_counts_pallas
        from repro.kernels.ops import pallas_interpret
        counts = windowed_counts_pallas(
            spp, sep, sgp, comm_range=comm_range, e_tau=e_tau,
            n_valid=n_valid, window=w, block=b,
            interpret=pallas_interpret())[:m]
    else:
        counts = _counts_block_jnp(spp, sep, sgp, comm_range=comm_range,
                                   e_tau=e_tau, n_valid=n_valid, window=w,
                                   block=b)[:m]
    if need is None:
        need = sg < n_valid
    covered = window_coverage(sp, se, sg, comm_range=comm_range,
                              e_tau=e_tau, n_valid=n_valid, window=w,
                              need=need)
    return counts, covered


def windowed_elect(pos: jax.Array, evals: jax.Array, *, comm_range: float,
                   top_m: int, e_tau: float, window: int,
                   impl: str = "jnp") -> Tuple[jax.Array, jax.Array]:
    """Single-device windowed election: (mask (N,) int32, overflow ()
    int32).  ``overflow == 0`` certifies the mask bit-identical to
    ``neighbor_elect_ref``; the caller falls back to the dense election
    otherwise."""
    n = pos.shape[0]
    order = jnp.argsort(pos)                  # stable: ties keep id order
    sp = pos[order]
    se = evals[order]
    sg = order.astype(jnp.int32)              # global id = the tie-break
    counts, covered = sorted_window_counts(
        sp, se, sg, comm_range=comm_range, e_tau=e_tau, n_valid=n,
        window=window, need=jnp.ones((n,), bool), impl=impl)
    sel = ((se >= e_tau) & (counts < top_m)).astype(jnp.int32)
    mask = jnp.zeros((n,), jnp.int32).at[order].set(sel)
    return mask, (~covered).astype(jnp.int32)


# --------------------------------------------------------------------------
# shard_map interior: segment re-bucketing + ppermute halo ring
# --------------------------------------------------------------------------

def ring_hops(comm_range: float, road_length: float, n_shards: int) -> int:
    """Adjacent-segment hops whose span covers ``comm_range``."""
    segw = road_length / n_shards
    return max(1, int(math.ceil(comm_range / segw)))


def ring_halo_elect(pos: jax.Array, evals: jax.Array, gid: jax.Array,
                    valid: jax.Array, *, axis: str, n: int, n_shards: int,
                    shard_n: int, comm_range: float, top_m: int,
                    e_tau: float, road_length: float, window: int,
                    capacity: int) -> Tuple[jax.Array, jax.Array]:
    """The windowed DCS election inside a ``("clients",)`` shard_map.

    Per device (= road segment owner):

    1. route every local client to its segment's owner with ONE tiled
       ``all_to_all`` of fixed ``(K, capacity)`` buffers (slot overflow
       -> flag);
    2. sort the received bucket by position; pull ``h`` boundary halo
       strips of width ``window`` from each ring neighbour by
       ``ppermute`` (strip overflow -> flag; strips that would wrap the
       road end are masked empty — reference distance is linear);
    3. merge + windowed counts (coverage shortfall -> flag), elect;
    4. inverse ``all_to_all`` routes each client's bit back to its
       owner's slot.

    Returns ``(mask (shard_n,) int32, overflow () int32 — this device's
    local flag; callers pmax it)``.  ``overflow == 0`` on every device
    certifies bit-identity with the gathered dense election."""
    k = n_shards
    segw = road_length / k
    h = ring_hops(comm_range, road_length, k)
    cap = capacity
    w = min(int(window), k * cap)
    i = jax.lax.axis_index(axis)
    # float-safety margin for the segment-boundary thresholds: widening
    # only adds candidates (masked later by the exact distance compare)
    margin = 1e-4 * road_length + 1e-6

    # -- 1. bucket clients by road segment, fixed (K, cap) send slots --
    seg = jnp.clip(jnp.floor(pos * (k / road_length)), 0, k - 1)
    seg = jnp.where(valid, seg.astype(jnp.int32), k)     # dummies drop
    order = jnp.argsort(seg)                             # stable
    sseg = seg[order]
    starts = jnp.searchsorted(sseg, jnp.arange(k))
    rank = jnp.arange(shard_n) - starts[jnp.clip(sseg, 0, k - 1)]
    send_ovf = jnp.any((sseg < k) & (rank >= cap))
    row = jnp.where((sseg < k) & (rank < cap), sseg, k)  # row k = dropped
    col = jnp.clip(rank, 0, cap - 1)

    def scatter(x, fill):
        buf = jnp.full((k + 1, cap), fill, x.dtype)
        return buf.at[row, col].set(x[order])[:k]

    bpos = scatter(pos.astype(jnp.float32), SENT_POS)
    bev = scatter(evals.astype(jnp.float32), SENT_EV)
    bgid = scatter(gid.astype(jnp.int32), n)

    def a2a(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    rpos, rev, rgid = a2a(bpos), a2a(bev), a2a(bgid)

    # -- 2. sort my segment's bucket, exchange halo strips -------------
    s = k * cap
    fpos, fev, fgid = rpos.reshape(s), rev.reshape(s), rgid.reshape(s)
    border = jnp.argsort(fpos)
    sp, se, sg = fpos[border], fev[border], fgid[border]
    n_real = jnp.searchsorted(sp, SENT_POS / 2.0)

    def suffix_strip(thr):
        """My clients with pos >= thr (capped at ``w``, overflow-flagged)."""
        start = jnp.searchsorted(sp, thr, side="left")
        cnt = jnp.maximum(n_real - start, 0)
        base = jnp.clip(jnp.minimum(start, s - w), 0, s - w)
        j = base + jnp.arange(w)
        ok = (j >= start) & (j < n_real)
        return (jnp.where(ok, jax.lax.dynamic_slice(sp, (base,), (w,)),
                          SENT_POS),
                jnp.where(ok, jax.lax.dynamic_slice(se, (base,), (w,)),
                          SENT_EV),
                jnp.where(ok, jax.lax.dynamic_slice(sg, (base,), (w,)), n),
                cnt > w)

    def prefix_strip(thr):
        """My clients with pos <= thr (capped at ``w``, overflow-flagged)."""
        end = jnp.minimum(jnp.searchsorted(sp, thr, side="right"), n_real)
        ok = jnp.arange(w) < end
        return (jnp.where(ok, sp[:w], SENT_POS),
                jnp.where(ok, se[:w], SENT_EV),
                jnp.where(ok, sg[:w], n),
                end > w)

    strips = []
    strip_ovf = jnp.bool_(False)
    for d in range(1, h + 1):
        # strip for receiver i+d: my suffix within comm_range of their
        # left edge; wrapped receivers (linear road!) get nothing
        rj = i + d
        thr = jnp.where(rj >= k, jnp.float32(SENT_POS),
                        rj * segw - comm_range - margin)
        spb, seb, sgb, so = suffix_strip(thr)
        strip_ovf |= so
        fwd = [(src, (src + d) % k) for src in range(k)]
        strips.append(tuple(jax.lax.ppermute(z, axis, fwd)
                            for z in (spb, seb, sgb)))
        # strip for receiver i-d: my prefix within comm_range of their
        # right edge
        lj = i - d
        thr_hi = jnp.where(lj < 0, jnp.float32(-SENT_POS),
                           (lj + 1) * segw + comm_range + margin)
        spb, seb, sgb, so = prefix_strip(thr_hi)
        strip_ovf |= so
        bwd = [(src, (src - d) % k) for src in range(k)]
        strips.append(tuple(jax.lax.ppermute(z, axis, bwd)
                            for z in (spb, seb, sgb)))

    # -- 3. merge own + halo candidates, windowed election -------------
    mpos = jnp.concatenate([sp] + [st[0] for st in strips])
    mev = jnp.concatenate([se] + [st[1] for st in strips])
    mgid = jnp.concatenate([sg] + [st[2] for st in strips])
    tag = jnp.concatenate([jnp.arange(s, dtype=jnp.int32),
                           jnp.full(2 * h * w, s, jnp.int32)])
    morder = jnp.argsort(mpos)
    msp, mse, msg, mtag = (mpos[morder], mev[morder], mgid[morder],
                           tag[morder])
    counts, covered = sorted_window_counts(
        msp, mse, msg, comm_range=comm_range, e_tau=e_tau, n_valid=n,
        window=w, need=(mtag < s) & (msg < n))
    sel = ((mse >= e_tau) & (counts < top_m)
           & (msg < n)).astype(jnp.int32)

    # -- 4. scatter back: merged -> bucket slots -> inverse a2a --------
    sel_sorted = jnp.zeros((s,), jnp.int32).at[mtag].set(sel, mode="drop")
    sel_bucket = jnp.zeros((s,), jnp.int32).at[border].set(sel_sorted)
    back = a2a(sel_bucket.reshape(k, cap))    # tiled a2a is an involution
    got = jnp.where((row < k),
                    back[jnp.clip(row, 0, k - 1), col], 0)
    mask = jnp.zeros((shard_n,), jnp.int32).at[order].set(got)
    ovf = (send_ovf | strip_ovf | ~covered).astype(jnp.int32)
    return mask, ovf


def sharded_topk_mask(evals: jax.Array, gid: jax.Array, valid: jax.Array,
                      *, axis: str, n: int, shard_n: int,
                      k_top: int) -> jax.Array:
    """Hierarchical global top-k inside a shard_map: local top-k per
    shard, one tiny ``all_gather`` of the K*k (value, gid) candidates,
    global top-k over the flattened list.

    Exact vs ``lax.top_k`` on the gathered (N,) vector *including* ties:
    ``top_k`` breaks equal values by lowest index, per-shard candidates
    keep ascending local order among ties, and the shard-major flat
    layout makes flat order == gid order among any tied value — so the
    winner set (and hence the mask) is bit-identical."""
    kloc = min(k_top, shard_n)
    ev_m = jnp.where(valid, evals, -jnp.inf)
    v, li = jax.lax.top_k(ev_m, kloc)
    g = gid[li].astype(jnp.int32)
    cv = jax.lax.all_gather(v, axis)          # (K, kloc)
    cg = jax.lax.all_gather(g, axis)
    _, sidx = jax.lax.top_k(cv.reshape(-1), k_top)
    winners = cg.reshape(-1)[sidx]
    mask = (gid[:, None] == winners[None, :]).any(axis=1)
    return (mask & valid).astype(jnp.int32)
