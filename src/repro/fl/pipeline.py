"""Pure staged round pipeline (paper Alg. 1 steps 1-7 as data flow).

``FLSimulation.run_round`` used to be host-driven: mobility, features,
fuzzy evaluation, selection and the Eq. 6 deadline mask each round-trip
through numpy, so nothing above the per-group trainer could be vmapped
over seeds or sharded over devices.  This module splits the round into
**pure stage functions** with explicit state-in/state-out signatures:

    positions(statics, cfg, t)                    -> (N,) road positions
    features(statics, cfg, params, t, net_key)    -> (pos, raw (N, 4))
    evaluate(statics, feats_raw)                  -> (N,) fuzzy evals
    select(cfg, pos, evals, sel_key)              -> (N,) int32 mask
    deadline_filter(statics, cfg, pos, mask, key) -> (survivors, n_straggler)
    train_groups(...) / aggregate(...)            -> new global params

The probe -> evaluate -> select -> deadline prefix is jax-traceable end
to end and compiles as ONE jitted function (``selection_prefix``) with
no host round-trips; survivor indices cross to the host exactly once, at
the cohort gather in ``train_groups``.  ``selection_prefix_seeds`` vmaps
the same prefix across a stacked seed axis — the multi-seed sweep
harness (``repro.launch.sweep``) evaluates S seeds' selection stages in
a single dispatch.

Pipeline state is split by trace role:

- ``RoundStatics``: a pytree of arrays that never change across rounds
  (mobility constants, slowdowns, the packed Eq. 7 probe tensors, the
  fuzzy membership parameters).  Leaves, so a leading seed axis can be
  stacked on for ``vmap``.
- ``StageConfig``: a frozen (hashable) dataclass of scalars — scheme,
  selection/timing/network parameters — passed as a jit-static.
- per-round inputs: the round index and base PRNG keys (folded per
  round *inside* the trace, so the prefix is deterministic in
  ``(statics, params, rnd, keys)`` and re-runnable for any round).

Randomness: the stateful numpy generators of ``CellularNetwork`` are
replaced by explicit jax keys — the Reno CWND predictor and the upload
shadowing each draw from ``fold_in(net_key, rnd)``, and the predictor's
pinned channel realization (``default_rng(0)`` in the host model) maps
to a constant key.  Eq. 8 normalization happens inside the fuzzy kernel
(``kops.fuzzy_eval(..., normalize=True)``), so ``features`` emits *raw*
columns [|D_i|, TA bps, 1/C_i, LF].
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.rules import build_rule_table
from repro.core.selection import selection_stats
from repro.fl.aggregation import fedavg_masked, fedavg_sums
from repro.fl.client import (dataset_loss_packed, local_train_batch,
                             local_train_batch_donated)
from repro.fl.mobility import coverage_active, positions_jax
from repro.fl.schemes import ShardCtx, get_scheme
from repro.fl.network import (NetworkConfig, cwnd_loss_fields,
                              pinned_channel_shadow,
                              predicted_throughput_from_fields,
                              predicted_throughput_jax,
                              upload_time_s_from_shadow, upload_time_s_jax)
from repro.fl.partition import ClientGroup
from repro.fl.timing import (TimingConfig, completes_before_deadline,
                             training_time_s)
from repro.fl.trace import RoundCounters
from repro.kernels import ops as kops
from repro.sharding.api import (CLIENT_AXIS, current_mesh, mesh_axis_size,
                                mesh_is_multihost, resolve_pspec)

Params = Any


# --------------------------------------------------------------------------
# pipeline state
# --------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("x0", "speeds", "jitter_phase", "slowdown", "n_valid",
                 "probe_images", "probe_labels", "probe_seg", "probe_counts",
                 "means", "sigmas", "level_centers"),
    meta_fields=())
@dataclasses.dataclass(frozen=True)
class RoundStatics:
    """Arrays that never change across rounds — the pure stages' closed-
    over world state, kept explicit so it can be stacked and vmapped."""
    # freeway mobility constants (fl/mobility.py)
    x0: jax.Array                 # (N,)
    speeds: jax.Array             # (N,)
    jitter_phase: jax.Array       # (N,)
    # per-client heterogeneity
    slowdown: jax.Array           # (N,) C_i >= 1
    n_valid: jax.Array            # (N,) float32 |D_i|
    # packed Eq. 7 probe (every client's valid probe samples, flat)
    probe_images: jax.Array       # (S, 28, 28, 1)
    probe_labels: jax.Array       # (S,)
    probe_seg: jax.Array          # (S,) client id per sample (N = padding)
    probe_counts: jax.Array       # (N,) samples per client
    # fuzzy evaluator membership parameters (core/fuzzy.py)
    means: jax.Array              # (4, 3)
    sigmas: jax.Array             # (4, 3)
    level_centers: jax.Array      # (9,)


@dataclasses.dataclass(frozen=True)
class StageConfig:
    """Hashable scalar configuration — one jit-static for the prefix."""
    scheme: str                   # dcs | ccs-fuzzy | random
    n_clients: int
    comm_range_m: float
    top_m: int
    e_tau: float
    n_clients_central: int
    model_bytes: float
    road_length_m: float
    speed_jitter: float
    timing: TimingConfig          # frozen: epochs/batch/B_exe/deadline
    network: NetworkConfig        # frozen: rates/shadowing/Reno params
    probe_batch: int = 128
    # device-resident fused probe->evaluate fast path (kops.probe_fuzzy):
    # default OFF — the staged jnp path below stays the bitwise-pinned
    # reference.  ON, the Eq. 7 probe forward, Eq. 8 normalization and
    # Mamdani inference run as one fused op (one Pallas launch under
    # REPRO_KERNEL_IMPL=pallas, one XLA program under the default jnp),
    # and the simulation packs the probe TIGHT (no per-client batch
    # alignment), so small clients stop paying dead probe rows.  Masks
    # are pinned bit-identical to the unfused path in
    # tests/test_probe_fuzzy.py; per-client losses may differ in the
    # last ulp (different — tighter — sample grouping).
    fused_probe: bool = False
    # coverage-window churn rate (event-driven fleet, ISSUE 6): clients
    # past (1-rate)*road_length are departed this round.  0.0 compiles
    # the exact churn-free graph — the gating is a static branch, so the
    # event server's sync-parity pin rests on an identical executable.
    churn_rate: float = 0.0
    # DCS election seam (ISSUE 9): "gather" keeps the dense O(N^2)
    # election (on all_gather'ed (N,) vectors in the sharded prefix);
    # "windowed" runs the O(N/K * W) position-sorted window — the
    # single-device sorted sweep, or the segment-bucketed ppermute halo
    # ring inside the shard_map.  Windowed rounds carry a runtime
    # ``elect_overflow`` flag; non-zero means a fixed window/buffer could
    # not hold every dense comparison and the round driver re-runs that
    # round with elect="gather" — so windowed masks are bit-identical to
    # the gather election whenever they are consumed.
    elect: str = "gather"
    elect_window: int = 0         # sorted neighbours per side (0 = auto)
    elect_capacity: int = 0       # shard->segment bucket slots (0 = auto)


@functools.lru_cache(maxsize=None)
def _rules() -> Tuple[np.ndarray, np.ndarray]:
    """The 81-rule base as host constants (static for the Pallas path)."""
    return build_rule_table()


# --------------------------------------------------------------------------
# stages (pure: explicit state in, arrays out)
# --------------------------------------------------------------------------

def positions(st: RoundStatics, cfg: StageConfig, t_s: jax.Array) -> jax.Array:
    """Mobility stage: wrapped freeway positions at time ``t_s``."""
    return positions_jax(st.x0, st.speeds, st.jitter_phase, t_s,
                         road_length_m=cfg.road_length_m,
                         speed_jitter=cfg.speed_jitter)


def features(st: RoundStatics, cfg: StageConfig, params: Params,
             t_s: jax.Array, net_key: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """Probe stage (Alg. 1 steps 1-2): raw multi-objective features.

    Returns ``(pos (N,), feats (N, 4))`` with *raw* columns
    [SQ=|D_i|, TA=predicted bps, CC=1/C_i, LF=Eq. 7 loss] — Eq. 8
    per-column max-scaling is folded into the ``evaluate`` stage's
    kernel, so no normalization happens here."""
    pos = positions(st, cfg, t_s)
    return pos, _raw_features(st, cfg, params, pos, net_key)


def _raw_features(st: RoundStatics, cfg: StageConfig, params: Params,
                 pos: jax.Array, net_key: jax.Array) -> jax.Array:
    """``features`` at given positions: the raw (N, 4) columns."""
    sq_raw = st.n_valid
    ta_raw = predicted_throughput_jax(cfg.network, pos, net_key)
    cc_raw = 1.0 / st.slowdown
    lf_raw = dataset_loss_packed(params, st.probe_images, st.probe_labels,
                                 st.probe_seg, st.probe_counts,
                                 n_clients=cfg.n_clients,
                                 batch=cfg.probe_batch)
    return jnp.stack([sq_raw, ta_raw, cc_raw, lf_raw],
                     axis=1).astype(jnp.float32)


def evaluate(st: RoundStatics, feats_raw: jax.Array) -> jax.Array:
    """Fuzzy evaluation stage (paper §5): raw (N, 4) -> (N,) on [0, 100].
    Eq. 8 normalization runs inside the kernel (``normalize=True``)."""
    table, levels = _rules()
    return kops.fuzzy_eval(feats_raw, st.means, st.sigmas, table, levels,
                           st.level_centers, normalize=True)


def select(cfg: StageConfig, pos: jax.Array, evals: jax.Array,
           sel_key: jax.Array) -> jax.Array:
    """Selection stage (Alg. 1 step 4) -> int32 mask (N,).  Dispatches
    through the scheme registry (``fl/schemes.py``) — unknown names
    raise at trace time with the registered list."""
    return get_scheme(cfg.scheme).select(cfg, pos, evals, sel_key)


def deadline_filter(st: RoundStatics, cfg: StageConfig, pos: jax.Array,
                    mask: jax.Array, upload_key: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Eq. 6 straggler stage: ``(survivors (N,) bool, n_straggler)``."""
    train_t = training_time_s(cfg.timing, st.slowdown, st.n_valid)
    upload_t = upload_time_s_jax(cfg.network, pos, cfg.model_bytes,
                                 upload_key)
    ok = completes_before_deadline(cfg.timing, train_t, upload_t)
    selected = mask > 0
    return selected & ok, (selected & ~ok).sum()


def completion_time_s(st: RoundStatics, cfg: StageConfig, pos: jax.Array,
                      upload_key: jax.Array, t_s: jax.Array) -> jax.Array:
    """Absolute per-client upload-completion instants (N,) — the event-
    driven server's landing-tick input.  Draws the same shadow as
    ``deadline_filter`` from the same key (XLA CSEs the duplicate inside
    the jitted prefix), so ``t_done <= t_s + deadline`` iff the client
    survives Eq. 6."""
    train_t = training_time_s(cfg.timing, st.slowdown, st.n_valid)
    upload_t = upload_time_s_jax(cfg.network, pos, cfg.model_bytes,
                                 upload_key)
    return t_s + train_t + upload_t


def _prefix(st: RoundStatics, params: Params, rnd: jax.Array,
            sel_key: jax.Array, net_key: jax.Array, *,
            cfg: StageConfig) -> Dict[str, jax.Array]:
    """Unjitted prefix body (also the vmap target)."""
    t_s = rnd.astype(jnp.float32) * cfg.timing.deadline_s
    k_sel = jax.random.fold_in(sel_key, rnd)
    k_pred, k_upload = jax.random.split(jax.random.fold_in(net_key, rnd))
    # the named scopes (trace.SCOPES) label each stage's ops in the
    # compiled HLO's op_name, and so in a device trace
    with jax.named_scope("positions"):
        pos = positions(st, cfg, t_s)
    with jax.named_scope("probe"):
        if cfg.fused_probe:
            # fused fast path: probe forward + Eq. 8 + Mamdani as one op
            # — a single kernel launch on the Pallas impl, one fused XLA
            # subgraph on the jnp impl (plus the tight probe pack built
            # by FLSimulation when the flag is on)
            ta_raw = predicted_throughput_jax(cfg.network, pos, k_pred)
            aux = jnp.stack([st.n_valid, ta_raw, 1.0 / st.slowdown],
                            axis=1).astype(jnp.float32)
            table, levels = _rules()
            feats, evals = kops.probe_fuzzy(
                params, st.probe_images, st.probe_labels, st.probe_seg,
                st.probe_counts, aux, st.means, st.sigmas, table, levels,
                st.level_centers, n_clients=cfg.n_clients,
                batch=cfg.probe_batch)
        else:
            feats = _raw_features(st, cfg, params, pos, k_pred)
            evals = evaluate(st, feats)
    with jax.named_scope("elect"):
        # churn stage (event-driven fleet): departed clients neither
        # report evaluations nor get selected.  Statically gated —
        # churn_rate == 0 compiles the exact pre-churn graph, which the
        # event server's sync-parity pin (tests/test_async.py) rests on.
        if cfg.churn_rate > 0.0:
            active = coverage_active(pos, road_length_m=cfg.road_length_m,
                                     churn_rate=cfg.churn_rate)
            evals = jnp.where(active, evals, 0.0)
        scheme = get_scheme(cfg.scheme)
        windowed = None
        if cfg.elect == "windowed" and scheme.select_windowed is not None:
            windowed = scheme.select_windowed(cfg, pos, evals, k_sel)
        if windowed is not None:
            mask, elect_overflow = windowed
        else:
            mask = select(cfg, pos, evals, k_sel)
            elect_overflow = jnp.int32(0)
        if cfg.churn_rate > 0.0:
            mask = jnp.where(active, mask, 0)
        stats = selection_stats(mask, evals)
    with jax.named_scope("deadline"):
        survivors, n_straggler = deadline_filter(st, cfg, pos, mask,
                                                 k_upload)
        # event-server inputs: absolute completion instants + presence
        # at upload time (a client leaving coverage mid-training/upload
        # loses its pending update)
        t_done = completion_time_s(st, cfg, pos, k_upload, t_s)
        if cfg.churn_rate > 0.0:
            pos_done = positions_jax(st.x0, st.speeds, st.jitter_phase,
                                     t_done,
                                     road_length_m=cfg.road_length_m,
                                     speed_jitter=cfg.speed_jitter)
            alive_at_done = coverage_active(pos_done,
                                            road_length_m=cfg.road_length_m,
                                            churn_rate=cfg.churn_rate)
            n_active = active.sum()
        else:
            alive_at_done = jnp.ones_like(survivors)
            n_active = jnp.asarray(cfg.n_clients, jnp.int32)
    return {"pos": pos, "feats": feats, "evals": evals, "mask": mask,
            "survivors": survivors, "n_straggler": n_straggler,
            "t_done": t_done, "alive_at_done": alive_at_done,
            "n_active": n_active,
            "n_selected": stats["n_selected"],
            "n_survivor": survivors.sum(),
            "mean_eval_selected": stats["mean_eval_selected"],
            "elect_overflow": elect_overflow}


@functools.partial(jax.jit, static_argnames=("cfg",))
def selection_prefix(st: RoundStatics, params: Params, rnd: jax.Array,
                     sel_key: jax.Array, net_key: jax.Array, *,
                     cfg: StageConfig) -> Dict[str, jax.Array]:
    """The probe -> evaluate -> select -> deadline prefix as ONE compiled
    function: no host round-trips between stages.  ``rnd`` is a traced
    int32 scalar, so every round shares a single executable."""
    return _prefix(st, params, rnd, sel_key, net_key, cfg=cfg)


def _prefix_seeds_body(st: RoundStatics, params: Params,
                       rnd: jax.Array, sel_keys: jax.Array,
                       net_keys: jax.Array, *,
                       cfg: StageConfig) -> Dict[str, jax.Array]:
    return jax.vmap(
        lambda s, p, ks, kn: _prefix(s, p, rnd, ks, kn, cfg=cfg)
    )(st, params, sel_keys, net_keys)


selection_prefix_seeds = functools.partial(
    jax.jit, static_argnames=("cfg",))(_prefix_seeds_body)
selection_prefix_seeds.__doc__ = """The prefix vmapped across a leading
seed axis.

``st``/``params`` carry stacked ``(S, ...)`` leaves (one slice per
seed — same shapes, different data/partitions), ``sel_keys``/
``net_keys`` are ``(S,)``-leading key arrays.  One dispatch evaluates
all S seeds' selection stages for round ``rnd``."""

# The round-ahead sweep scheduler re-stacks the per-seed params every
# round (a fresh (S, ...) buffer per dispatch) — donating them lets XLA
# reuse that allocation for the prefix's intermediates instead of
# round-tripping ~S x model_bytes through fresh buffers each round.
# Only for callers whose stacked params are single-use; the plain
# variant above keeps its inputs alive.
selection_prefix_seeds_donated = functools.partial(
    jax.jit, static_argnames=("cfg",),
    donate_argnums=(1,))(_prefix_seeds_body)


def stack_statics(statics: Sequence[RoundStatics]) -> RoundStatics:
    """Stack per-seed statics into one (S, ...)-leading pytree for
    ``selection_prefix_seeds`` (shapes must match across seeds — they do
    whenever the seeds share a partition profile)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *statics)


# --------------------------------------------------------------------------
# training stages (host gather -> device train -> aggregate)
# --------------------------------------------------------------------------

def cohort_bucket(k: int) -> int:
    """Cohort tensor size for k survivors: next multiple of 2, min 2 —
    jit compiles a handful of shapes no matter how the per-round
    selection count fluctuates.  The floor matters for capacity groups:
    a Table-3 big-group cohort of 1-2 must not train (and compile) 4
    padded 4500-sample slots."""
    return max(2, k + (k % 2))


def _count_cohort(counters: Optional[RoundCounters], k: int,
                 bucket: int) -> None:
    """Count ``k`` survivors trained in a bucket of ``bucket`` slots."""
    if counters is not None:
        counters.cohort_rows += k
        counters.cohort_pad_rows += bucket - k


def train_groups(params: Params, groups: Sequence[ClientGroup],
                 group_steps: Sequence[int], survivors: np.ndarray,
                 keys: jax.Array, *, epochs: int, batch_size: int,
                 lr: float, prox_mu: float, return_entries: bool = False,
                 counters: Optional[RoundCounters] = None
                 ) -> Optional[Tuple]:
    """Local-training stage (Eq. 1): one ``vmap(local_train)`` per
    capacity group over that group's surviving cohort.

    ``survivors`` is the single host-side crossing of the round — the
    cohort gather needs concrete indices to slice fixed-shape stacks.
    Returns ``(stacked models, weights)`` with padding duplicates at
    weight zero, or ``None`` for an empty round (no-op broadcast).
    Groups with an empty cohort are skipped — never padded from a
    nonexistent ``cohort[0]``.

    The cohort tensors gathered here are fresh per call, so the trainer
    runs with ``donate_argnums`` on them — the (bucket, cap, ...)
    stacks' buffers are recycled into the trained-model outputs instead
    of round-tripping through new allocations every round.

    ``return_entries=True`` (the event-driven server's pool path)
    returns ``(merged, weights (np), client_ids (np))`` instead — the
    per-row global client ids let the caller split the stack's FedAvg
    weights across aggregation ticks without re-gathering (padding rows
    keep weight zero and duplicate the cohort head's id).

    ``counters`` (a ``trace.RoundCounters``) counts the cohort rows and
    padding slots trained."""
    if not survivors.any():
        return None
    stacks, weights, row_ids = [], [], []
    for gi, g in enumerate(groups):
        cohort = np.where(survivors[g.client_ids])[0]       # group-local
        k = len(cohort)
        if k == 0:
            continue                         # empty cohort: skip group
        bucket = cohort_bucket(k)
        _count_cohort(counters, k, bucket)
        idx = np.concatenate([cohort, np.full(bucket - k, cohort[0])])
        stacked, _ = local_train_batch_donated(
            params, jnp.asarray(g.images[idx]), jnp.asarray(g.labels[idx]),
            jnp.asarray(g.n_valid[idx]),
            keys[jnp.asarray(g.client_ids[idx])],
            epochs=epochs, batch_size=batch_size,
            steps_per_epoch=group_steps[gi], lr=lr, prox_mu=prox_mu)
        w = g.n_valid[idx].astype(np.float32)
        w[k:] = 0.0                          # padding duplicates drop out
        stacks.append(stacked)
        weights.append(w)
        row_ids.append(g.client_ids[idx])
    merged = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *stacks)
    if return_entries:
        return merged, np.concatenate(weights), np.concatenate(row_ids)
    return merged, jnp.asarray(np.concatenate(weights))


def fedavg_round(merged: Params, weights: jax.Array) -> Params:
    """The round's FedAvg over the merged cohort stacks (Eq. 2)."""
    return fedavg_masked(merged, weights)


# the merged (sum-of-buckets, ...) model stack is the round's largest
# fresh buffer (bucket x ~1.66M floats) — donate it into the FedAvg; the
# executable is named jit_fedavg_round in a device trace
_fedavg_round_donated = jax.jit(fedavg_round, donate_argnums=(0,))


def aggregate(params: Params,
              trained: Optional[Tuple[Params, jax.Array]]) -> Params:
    """FedAvg stage (Eq. 2) over the survivors; an empty round returns
    the global model unchanged (no-op broadcast).  The merged per-group
    stacks are single-use, so they are donated into the average."""
    if trained is None:
        return params
    merged, weights = trained
    return _fedavg_round_donated(merged, weights)


# --------------------------------------------------------------------------
# mesh-sharded client axis (shard_map over a ("clients",) mesh)
#
# The same staged prefix, partitioned: every client-axis array (statics
# leaves, random fields, stage intermediates) lives as one shard per
# device, padded with masked dummy clients to a mesh multiple.  The few
# genuinely global steps are explicit collectives:
#
#   - the packed Eq. 7 probe reduces per-client loss sums with a psum
#     (each client's samples live wholly on its owner shard, so the psum
#     only adds exact zeros from the other devices — bitwise-neutral);
#   - the Eq. 8 column maxima are a pmax (max is associativity-exact);
#   - selection (DCS neighbour election windows / CCS quotas / stats)
#     runs on all_gather'ed (N,) evaluation+position vectors — the only
#     arrays that cross devices are N floats, never the (S, 28, 28, 1)
#     probe stacks or the per-group training tensors.
#
# PRNG parity: the channel/loss randomness is drawn as *global fields*
# with exactly the keys and shapes of the unsharded prefix
# (fl/network.py `*_from_fields` split), then padded and sharded like any
# other client-axis array — so a sharded round reproduces the
# single-device selection masks bit-for-bit (pinned in
# tests/test_sharding.py).
# --------------------------------------------------------------------------


def mesh_client_shards(mesh: Optional[Mesh]) -> int:
    """The client-axis partition factor of ``mesh`` (1 when unsharded)."""
    return mesh_axis_size(mesh, CLIENT_AXIS)


def active_client_mesh() -> Optional[Mesh]:
    """The ambient ``logical_sharding`` mesh iff it has a live
    ``clients`` axis — the launchers' ``--mesh clients=K`` activates one;
    unit tests and the single-device drivers see None."""
    mesh = current_mesh()
    return mesh if mesh_client_shards(mesh) > 1 else None


def pad_to_shards(n: int, shards: int) -> int:
    """Client count padded up to a mesh multiple (masked dummy clients —
    never a silent replicate-on-indivisible fallback)."""
    return -(-n // shards) * shards


@functools.lru_cache(maxsize=None)
def _sharded_prefix_fn(cfg: StageConfig, mesh: Mesh, seeds: bool):
    """Build (and cache) the jitted shard_map'd prefix for one
    (StageConfig, mesh) pair.  ``seeds=True`` vmaps the per-shard body
    over a leading seed axis inside the same shard_map — the sweep's
    multi-seed dispatch with every seed's client axis partitioned."""
    k = mesh_client_shards(mesh)
    n = cfg.n_clients
    n_pad = pad_to_shards(n, k)
    shard_n = n_pad // k
    pad = n_pad - n
    table, levels = _rules()

    def core(x0, speeds, jphase, slowdown, n_valid, pim, plb, pseg, counts,
             means, sigmas, centers, params, t_s, k_sel, pin_shadow,
             loss_u, up_shadow):
        """Per-device body: all (shard_n,)-leading arrays are this
        device's client shard; params/counts/membership params are
        replicated; ``pin_shadow``/``loss_u``/``up_shadow`` are the
        device's slice of the globally-drawn random fields."""
        i = jax.lax.axis_index(CLIENT_AXIS)
        gid = i * shard_n + jnp.arange(shard_n)
        valid = gid < n                      # False on dummy pad clients

        # stages under the unsharded prefix's named scopes
        # (trace.SCOPES); positions and raw features are elementwise
        with jax.named_scope("positions"):
            pos = positions_jax(x0, speeds, jphase, t_s,
                                road_length_m=cfg.road_length_m,
                                speed_jitter=cfg.speed_jitter)
        with jax.named_scope("probe"):
            ta = predicted_throughput_from_fields(cfg.network, pos,
                                                  pin_shadow, loss_u)
            # Eq. 7 over the local probe shard; every client's samples
            # live on its owner device, so the psum adds exact zeros
            # elsewhere.  The fused fast path swaps in the fused probe op
            # (one Pallas launch per shard under the pallas impl; the
            # psum seam below and the Eq. 8 pmax stay outside the kernel
            # by design).
            if cfg.fused_probe:
                lf_part = kops.probe_loss(params, pim, plb, pseg, counts,
                                          n_clients=n,
                                          batch=cfg.probe_batch)
            else:
                lf_part = dataset_loss_packed(params, pim, plb, pseg,
                                              counts, n_clients=n,
                                              batch=cfg.probe_batch)
            lf_full = jax.lax.psum(lf_part, CLIENT_AXIS)
            lf = jax.lax.dynamic_slice_in_dim(jnp.pad(lf_full, (0, pad)),
                                              i * shard_n, shard_n)
            feats = jnp.stack([n_valid, ta, 1.0 / slowdown, lf],
                              axis=1).astype(jnp.float32)

            # fuzzy evaluation with the Eq. 8 maxima pmax'd globally
            col_max = jax.lax.pmax(
                jnp.where(valid[:, None], feats, -jnp.inf).max(axis=0),
                CLIENT_AXIS)
            evals = kops.fuzzy_eval(feats, means, sigmas, table, levels,
                                    centers, normalize=True,
                                    col_maxima=col_max)
            evals = jnp.where(valid, evals, 0.0)

        with jax.named_scope("elect"):
            # churn stage (statically gated, exactly like the unsharded
            # prefix): departed clients report no evaluation and cannot be
            # selected; the active mask gathers with the evals so the
            # selection sees the identical (N,) inputs
            if cfg.churn_rate > 0.0:
                active = coverage_active(pos, road_length_m=cfg.road_length_m,
                                         churn_rate=cfg.churn_rate)
                evals = jnp.where(active, evals, 0.0)

            # stage: selection.  elect="windowed" keeps the election
            # shard-local — segment re-bucketing + a ppermute halo ring for
            # the DCS window, a hierarchical top-k for the CCS quota, and
            # psum'd stats — so no (N,) vector is ever gathered.  The gather
            # seam below remains the fallback (and the bit-identity anchor:
            # a non-zero overflow flag makes the round driver re-run the
            # round through it).
            scheme = get_scheme(cfg.scheme)
            windowed = None
            if cfg.elect == "windowed" and scheme.select_sharded is not None:
                ctx = ShardCtx(axis=CLIENT_AXIS, n=n, n_shards=k,
                               shard_n=shard_n, pad=pad, gid=gid, valid=valid)
                windowed = scheme.select_sharded(cfg, ctx, pos, evals, k_sel)
            if windowed is not None:
                mask, ovf_local = windowed
                mask = jnp.where(valid, mask, 0)
                if cfg.churn_rate > 0.0:
                    mask = jnp.where(active, mask, 0)
                elect_overflow = jax.lax.pmax(ovf_local, CLIENT_AXIS)
                n_sel = jax.lax.psum(mask.sum(), CLIENT_AXIS)
                ev_sel = jax.lax.psum((evals * mask).sum(), CLIENT_AXIS)
                mean_ev_sel = jnp.where(n_sel > 0,
                                        ev_sel / jnp.maximum(n_sel, 1), 0.0)
            else:
                ev_g = jax.lax.all_gather(evals, CLIENT_AXIS, tiled=True)[:n]
                pos_g = jax.lax.all_gather(pos, CLIENT_AXIS, tiled=True)[:n]
                mask_g = select(cfg, pos_g, ev_g, k_sel)
                if cfg.churn_rate > 0.0:
                    act_g = jax.lax.all_gather(active, CLIENT_AXIS,
                                               tiled=True)[:n]
                    mask_g = jnp.where(act_g, mask_g, 0)
                mask = jax.lax.dynamic_slice_in_dim(jnp.pad(mask_g, (0, pad)),
                                                    i * shard_n, shard_n)
                elect_overflow = jnp.int32(0)
                stats = selection_stats(mask_g, ev_g)
                n_sel = stats["n_selected"]
                mean_ev_sel = stats["mean_eval_selected"]

        with jax.named_scope("deadline"):
            # Eq. 6, shard-local again
            train_t = training_time_s(cfg.timing, slowdown, n_valid)
            upload_t = upload_time_s_from_shadow(cfg.network, pos,
                                                 cfg.model_bytes, up_shadow)
            ok = completes_before_deadline(cfg.timing, train_t, upload_t)
            selected = mask > 0
            survivors = selected & ok & valid
            n_straggler = jax.lax.psum((selected & ~ok & valid).sum(),
                                       CLIENT_AXIS)
            n_survivor = jax.lax.psum(survivors.sum(), CLIENT_AXIS)
            # event-server inputs, shard-local like the deadline stage
            t_done = t_s + train_t + upload_t
            if cfg.churn_rate > 0.0:
                pos_done = positions_jax(x0, speeds, jphase, t_done,
                                         road_length_m=cfg.road_length_m,
                                         speed_jitter=cfg.speed_jitter)
                alive_done = coverage_active(pos_done,
                                             road_length_m=cfg.road_length_m,
                                             churn_rate=cfg.churn_rate)
                n_active = jax.lax.psum((active & valid).sum(), CLIENT_AXIS)
            else:
                alive_done = jnp.ones_like(survivors)
                n_active = jnp.asarray(n, jnp.int32)
        return (pos, feats, evals, mask, survivors, n_straggler,
                t_done, alive_done, n_active,
                n_sel, n_survivor, mean_ev_sel, elect_overflow)

    def s(*tail):
        """Spec helper: prepend the (unsharded) seed axis when vmapped."""
        return P(None, *tail) if seeds else P(*tail)

    rep = P()
    in_specs = (s(CLIENT_AXIS), s(CLIENT_AXIS), s(CLIENT_AXIS),
                s(CLIENT_AXIS), s(CLIENT_AXIS),
                s(CLIENT_AXIS, None, None, None),    # probe images
                s(CLIENT_AXIS), s(CLIENT_AXIS),      # probe labels/seg
                rep, rep, rep, rep,                  # counts, memberships
                rep, rep, rep,                       # params, t_s, k_sel
                P(CLIENT_AXIS),                      # pinned shadow
                s(None, CLIENT_AXIS),                # cwnd loss field
                s(CLIENT_AXIS))                      # upload shadow
    out_specs = (s(CLIENT_AXIS), s(CLIENT_AXIS, None), s(CLIENT_AXIS),
                 s(CLIENT_AXIS), s(CLIENT_AXIS), rep,
                 s(CLIENT_AXIS), s(CLIENT_AXIS), rep,
                 rep, rep, rep, rep)
    body = core if not seeds else jax.vmap(
        core, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
                       None, 0, None, 0, 0))
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

    def run(st: RoundStatics, params: Params, rnd: jax.Array,
            sel_key: jax.Array, net_key: jax.Array):
        sample_ax = 1 if seeds else 0
        if st.probe_images.shape[sample_ax] % k != 0:
            raise ValueError(
                f"packed probe sample axis {st.probe_images.shape} not "
                f"divisible by {k} client shards — build the simulation "
                f"inside the mesh context so the probe packs per shard")
        t_s = rnd.astype(jnp.float32) * cfg.timing.deadline_s
        # per-round keys + global random fields, folded/drawn exactly as
        # the unsharded prefix folds/draws them (see _prefix)
        if seeds:
            k_sel = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                sel_key, rnd)
            folded = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                net_key, rnd)
            knet = jax.vmap(jax.random.split)(folded)
            loss_u = jax.vmap(lambda kk: cwnd_loss_fields(kk, n))(
                knet[:, 0])
            up_shadow = jax.vmap(lambda kk: jax.random.normal(kk, (n,)))(
                knet[:, 1])
        else:
            k_sel = jax.random.fold_in(sel_key, rnd)
            k_pred, k_upload = jax.random.split(
                jax.random.fold_in(net_key, rnd))
            loss_u = cwnd_loss_fields(k_pred, n)
            up_shadow = jax.random.normal(k_upload, (n,))
        pin_shadow = jnp.pad(pinned_channel_shadow(n), (0, pad))

        ax = 1 if seeds else 0

        def padc(x, value=0.0, axis=ax):
            widths = [(0, 0)] * x.ndim
            widths[axis] = (0, pad)
            return jnp.pad(x, widths, constant_values=value)

        out = sharded(
            padc(st.x0), padc(st.speeds), padc(st.jitter_phase),
            padc(st.slowdown, 1.0), padc(st.n_valid),
            st.probe_images, st.probe_labels, st.probe_seg,
            st.probe_counts, st.means, st.sigmas, st.level_centers,
            params, t_s, k_sel, pin_shadow,
            padc(loss_u, axis=loss_u.ndim - 1), padc(up_shadow))
        (pos, feats, evals, mask, survivors, n_strag, t_done, alive,
         n_active, n_sel, n_surv, mev, ovf) = out
        cut = (lambda x: x[:, :n]) if seeds else (lambda x: x[:n])
        res = {"pos": cut(pos), "feats": cut(feats), "evals": cut(evals),
               "mask": cut(mask), "survivors": cut(survivors),
               "n_straggler": n_strag, "t_done": cut(t_done),
               "alive_at_done": cut(alive), "n_active": n_active,
               "n_selected": n_sel, "n_survivor": n_surv,
               "mean_eval_selected": mev, "elect_overflow": ovf}
        if multihost:
            # every process consumes the full round state (masks feed the
            # host-side cohort gather on each host) — replicate outputs
            # so device_get works everywhere
            res = {key: jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P())) for key, v in res.items()}
        return res

    multihost = mesh_is_multihost(mesh)
    return jax.jit(run)


def selection_prefix_sharded(st: RoundStatics, params: Params,
                             rnd: jax.Array, sel_key: jax.Array,
                             net_key: jax.Array, *, cfg: StageConfig,
                             mesh: Mesh) -> Dict[str, jax.Array]:
    """``selection_prefix`` with the client axis partitioned over
    ``mesh``'s ``clients`` axis — same signature, same output dict, same
    masks bit-for-bit; requires the statics' probe packed for the mesh
    (``FLSimulation`` built inside the mesh context does this)."""
    return _sharded_prefix_fn(cfg, mesh, False)(st, params, rnd, sel_key,
                                                net_key)


def selection_prefix_seeds_sharded(st: RoundStatics, params: Params,
                                   rnd: jax.Array, sel_keys: jax.Array,
                                   net_keys: jax.Array, *, cfg: StageConfig,
                                   mesh: Mesh) -> Dict[str, jax.Array]:
    """``selection_prefix_seeds`` over a client mesh: one dispatch
    evaluates S seeds' selection stages with every seed's client axis
    sharded over the same devices."""
    return _sharded_prefix_fn(cfg, mesh, True)(st, params, rnd, sel_keys,
                                               net_keys)


# -- sharded training stages ------------------------------------------------

def cohort_bucket_sharded(k: int, shards: int) -> int:
    """``cohort_bucket`` rounded up to a mesh multiple, so every device
    trains an equal slice of the group's cohort (padding duplicates at
    weight zero, exactly like the unsharded bucket)."""
    return pad_to_shards(cohort_bucket(k), shards)


@functools.lru_cache(maxsize=None)
def _sharded_group_trainer(mesh: Mesh, epochs: int, batch_size: int,
                           steps_per_epoch: int, lr: float, prox_mu: float):
    """One capacity group's shard_map'd trainer: each device runs
    ``local_train_batch`` over its cohort shard and the weighted model
    sum finishes with a cross-device psum (``fedavg_sums``) — the
    ``(bucket, cap, ...)`` stack never materializes on one chip."""

    def body(params, images, labels, n_valid, keys, w):
        stacked, _ = local_train_batch(
            params, images, labels, n_valid, keys, epochs=epochs,
            batch_size=batch_size, steps_per_epoch=steps_per_epoch, lr=lr,
            prox_mu=prox_mu)
        return fedavg_sums(stacked, w, axis_name=CLIENT_AXIS)

    c = P(CLIENT_AXIS)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(), c, c, c, c, c),
                            out_specs=(P(), P()), check_vma=False)
    # the cohort shards are device_put fresh per round by the gather
    # below — donate them so the per-device training buffers recycle
    return jax.jit(sharded, donate_argnums=(1, 2, 3, 4, 5))


def train_group_cohort_sharded(params: Params, group: ClientGroup,
                               steps_per_epoch: int, idx: np.ndarray,
                               weights: np.ndarray, keys: jax.Array,
                               mesh: Mesh, *, epochs: int, batch_size: int,
                               lr: float, prox_mu: float
                               ) -> Tuple[Params, jax.Array]:
    """Dispatch one group's gathered cohort to the sharded trainer.

    The host-side gather places each device's shard directly via
    ``NamedSharding`` (``resolve_pspec`` with ``require=`` — the client
    partition may never silently replicate), so only ``len(idx)/K``
    clients' tensors are ever transferred to any one device.  Returns the
    psum'd ``(weighted model sum, weight total)`` partial aggregates."""
    rules = {CLIENT_AXIS: CLIENT_AXIS}
    images = group.images[idx]
    im_spec = resolve_pspec(mesh, rules, (CLIENT_AXIS,) + (None,) *
                            (images.ndim - 1), images.shape,
                            require=(CLIENT_AXIS,))
    row_spec = resolve_pspec(mesh, rules, (CLIENT_AXIS,), (len(idx),),
                             require=(CLIENT_AXIS,))

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    trainer = _sharded_group_trainer(mesh, epochs, batch_size,
                                     steps_per_epoch, lr, prox_mu)
    return trainer(params, put(images, im_spec),
                   put(group.labels[idx], row_spec),
                   put(group.n_valid[idx], row_spec),
                   put(np.asarray(keys), row_spec),
                   put(weights.astype(np.float32), row_spec))


def train_groups_sharded(params: Params, groups: Sequence[ClientGroup],
                         group_steps: Sequence[int], survivors: np.ndarray,
                         keys: jax.Array, mesh: Mesh, *, epochs: int,
                         batch_size: int, lr: float, prox_mu: float,
                         weight_scale: float = 1.0,
                         counters: Optional[RoundCounters] = None
                         ) -> Optional[Tuple[Params, jax.Array]]:
    """Mesh-sharded ``train_groups``: per capacity group, each device
    trains its shard of the surviving cohort; the Eq. 2 numerator/
    denominator accumulate across groups and devices (psum inside the
    trainer, plain adds across groups).  Returns the unnormalized
    ``(sum_i w_i model_i, sum_i w_i)`` or None for an empty round.

    ``weight_scale`` multiplies every cohort weight — the event-driven
    server's per-tick staleness factor (one landing tick shares one
    delay, hence one scalar).  The default 1.0 leaves the weights
    bitwise untouched (the sync-parity pin).  ``counters`` as in
    ``train_groups``."""
    if not survivors.any():
        return None
    shards = mesh_client_shards(mesh)
    num_tot, den_tot = None, None
    for gi, g in enumerate(groups):
        cohort = np.where(survivors[g.client_ids])[0]       # group-local
        k = len(cohort)
        if k == 0:
            continue                         # empty cohort: skip group
        bucket = cohort_bucket_sharded(k, shards)
        _count_cohort(counters, k, bucket)
        idx = np.concatenate([cohort, np.full(bucket - k, cohort[0])])
        w = g.n_valid[idx].astype(np.float32)
        if weight_scale != 1.0:
            w *= np.float32(weight_scale)
        w[k:] = 0.0                          # padding duplicates drop out
        num, den = train_group_cohort_sharded(
            params, g, group_steps[gi], idx, w,
            keys[jnp.asarray(g.client_ids[idx])], mesh, epochs=epochs,
            batch_size=batch_size, lr=lr, prox_mu=prox_mu)
        num_tot = num if num_tot is None else jax.tree.map(jnp.add,
                                                           num_tot, num)
        den_tot = den if den_tot is None else den_tot + den
    if num_tot is None:
        return None
    return num_tot, den_tot


def _finish_sharded_aggregate(num: Params, den: jax.Array,
                              params: Params) -> Params:
    inv = 1.0 / jnp.maximum(den, 1e-9)
    return jax.tree.map(lambda s_leaf, p: (s_leaf * inv).astype(p.dtype),
                        num, params)


# the psum'd weighted-sum tree is fresh per round — donate it into the
# normalized global model
_finish_sharded_aggregate_donated = jax.jit(_finish_sharded_aggregate,
                                            donate_argnums=(0,))


def aggregate_sharded(params: Params,
                      trained: Optional[Tuple[Params, jax.Array]]) -> Params:
    """Finish Eq. 2 from the sharded trainer's psum'd partial sums; an
    empty round returns the global model unchanged."""
    if trained is None:
        return params
    num, den = trained
    return _finish_sharded_aggregate_donated(num, den, params)
