"""The federated round engine (paper Alg. 1 + §6 simulator).

One ``FLSimulation`` couples: the synthetic non-iid dataset partition,
freeway mobility, the cellular/CWND network model, the Eq. 6 timing model,
the fuzzy evaluator and one of the three selection schemes.  Each round:

  1. broadcast: every participant receives the global model;
  2. probe: every participant computes Eq. 7 (loss of the *global* model
     over its local data, no update);
  3. evaluate: fuzzy evaluation from (SQ, TA, CC, LF), locally;
  4. select: dcs (neighbour election) / ccs-fuzzy (server top-n) /
     random (server uniform);
  5. train: selected clients run Eq. 1 local SGD;
  6. deadline: models whose train+upload time exceeds the deadline are
     discarded (stragglers);
  7. aggregate: FedAvg (Eq. 2) over the survivors;
  8. account: state-maintenance vs evaluation-exchange communication.

Client datasets are stored **capacity-grouped**: ``stack_clients``
buckets clients by quantity-rounded-to-batches capacity and returns one
fixed-shape ``ClientGroup`` per bucket (Table-3 full profile: a 4500-cap
group of 12 and a 60-cap group of 18).  Two engines implement steps
2/5/7 over these groups:

- ``engine="batched"`` (default): the Eq. 7 probe is one fused forward
  pass over a packed concatenation of every client's valid probe samples
  (padding rows cost nothing), local SGD is one ``vmap(local_train)``
  per capacity group over that group's surviving cohort (gathered into a
  bucketed fixed-size tensor so jit sees a handful of shapes per group),
  and the selection/deadline mask is folded into the FedAvg weights —
  all groups aggregate in a single ``fedavg_masked`` over concatenated
  per-group stacks and weights.  Small-capacity cohorts train their own
  few steps per epoch instead of the largest group's.
- ``engine="loop"``: the reference per-client Python loop, kept for
  parity testing (see tests/test_engine_parity.py).  It trains each
  client at its own group's capacity, so the two engines stay
  numerically equivalent sample-for-sample.

Both engines draw per-client training randomness from the same
``fold_in(round, client)`` schedule, and both treat an **empty round**
(no client survives selection + deadline — e.g. every evaluation below
``E_tau``) as a no-op broadcast: the global model is unchanged, exactly.
Per-group empty cohorts are skipped the same way — a group never pads
from an empty cohort.

Steps 1-4 and 6 (probe -> fuzzy evaluate -> select -> deadline) are the
**staged pure pipeline** of ``fl/pipeline.py``: one jitted
``selection_prefix`` with no host round-trips, shared by both engines —
``FLSimulation`` is a thin stateful wrapper that holds the statics /
PRNG bases and crosses the survivor mask to the host exactly once, at
the cohort gather.  The sweep harness (``repro.launch.sweep``) drives
the same prefix vmapped across seeds.

Constructed inside an active ``logical_sharding`` context whose mesh has
a live ``clients`` axis (the launchers' ``--mesh clients=K``), the
simulation partitions the in-round client axis over that mesh: the
prefix runs as ``selection_prefix_sharded`` (same masks bit-for-bit),
the probe packs one sample region per shard, and the batched engine
trains each capacity group through the shard_map'd grouped trainer with
a cross-device psum'd FedAvg (``train_groups_sharded``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN_CFG
from repro.core.fuzzy import FuzzyEvaluator, FuzzyEvaluatorConfig
from repro.core.overhead import (accumulated_time_s, IoVParams,
                                 model_upload_bytes,
                                 state_maintenance_bytes)
from repro.data.synthetic import make_dataset, train_test_split
from repro.fl import pipeline, trace
from repro.fl.aggregation import fedavg
from repro.fl.client import (evaluate_accuracy_async, local_train,
                             local_train_batch_donated)
from repro.fl.mobility import FreewayMobility, MobilityConfig
from repro.fl.network import NetworkConfig
from repro.fl.partition import (PartitionConfig, partition,
                                shard_client_range, stack_clients,
                                steps_per_epoch)
from repro.fl.runconfig import ENGINES, RunConfig, resolve_run
from repro.fl.schemes import get_scheme
from repro.launch import faults
from repro.models.cnn import init_cnn
from repro.sharding.api import CLIENT_AXIS, mesh_is_multihost


def build_round_checkpointer(run_cfg: RunConfig, checkpointer=None):
    """The driver-facing checkpoint seam (ISSUE 10): an explicit
    ``RoundCheckpointer`` wins; otherwise one is built from the run
    config's ``checkpoint_dir``/``checkpoint_every``; ``None`` disables
    checkpointing entirely."""
    if checkpointer is not None:
        return checkpointer
    if run_cfg.checkpoint_dir:
        from repro.train.checkpoint import RoundCheckpointer
        return RoundCheckpointer(run_cfg.checkpoint_dir,
                                 every=run_cfg.checkpoint_every)
    return None


def resume_rows(driver, ckpt, resume: bool):
    """Restore ``driver`` (an ``FLSimulation`` or ``EventDrivenServer``)
    from the latest good snapshot -> ``(rows_so_far, start_round)``.

    Corrupt snapshots were already skipped (with a warning) inside
    ``latest_good``; no snapshot at all means a fresh start — resume is
    idempotent and safe to pass unconditionally."""
    if not resume or ckpt is None:
        return [], 0
    got = ckpt.latest_good()
    if got is None:
        return [], 0
    rnd, state, extra = got
    driver.restore_state(state, extra)
    return [dict(r) for r in extra.get("rows", [])], rnd + 1


def checkpoint_round(driver, ckpt, rnd: int, rows, *,
                     lead: bool = True) -> None:
    """Snapshot the end-of-round state when due (lead process only),
    then announce the fault-injection events the chaos suite keys on."""
    with trace.span(trace.CHECKPOINT, round=rnd):
        if ckpt is not None and lead and ckpt.due(rnd):
            ckpt.save_round(rnd, driver.capture_state(),
                            extra={"rows": rows, "next_round": rnd + 1})
            faults.fire("checkpoint-saved", round=rnd)
        faults.fire("round-done", round=rnd)


def _elect_overflowed(host: Dict) -> bool:
    return int(np.max(host.get("elect_overflow", 0))) != 0


@dataclass
class FLSimConfig:
    scheme: str = "dcs"                  # any registered scheme
                                         # (fl/schemes.py; builtins:
                                         # dcs | ccs-fuzzy | random)
    # deprecated (one release): engine/fused_probe/overlap_rounds moved
    # to RunConfig — a non-None value here still works but warns and is
    # folded into the run config (repro.fl.runconfig.resolve_run)
    engine: Optional[str] = None
    n_rounds: int = 20
    n_clients_central: int = 5           # CCS/random pick (Table 3)
    comm_range_m: float = 200.0
    top_m: int = 2                       # per 200 m area (Table 3)
    e_tau: float = 30.0
    local_epochs: int = 2                # paper: 30; scaled for CPU budget
    batch_size: int = 20
    lr: float = 0.05
    prox_mu: float = 0.0                 # >0 enables FedProx
    deadline_s: float = 60.0             # see fl/timing.py docstring
    model_bytes: float = 5.2e6
    state_bytes: float = 100.0
    eval_bytes: float = 30.0
    state_interval_s: float = 1.0
    slowdown_range: tuple = (1.0, 4.0)   # C_i heterogeneity
    probe_samples: int = 256             # Eq. 7 subsample (paper: all
                                         # samples; ranking-equivalent)
    samples_per_class: int = 6600        # source pool size (>= per-class
                                         # demand of the no-dup partition)
    uniform_capacity: bool = False       # True: single max-cap group (the
                                         # pre-grouping layout; benchmark
                                         # baseline only)
    fused_probe: Optional[bool] = None   # deprecated: RunConfig.fused_probe
    overlap_rounds: Optional[bool] = None  # deprecated:
                                         # RunConfig.overlap_rounds
    seed: int = 0
    partition: PartitionConfig = field(default_factory=PartitionConfig)
    mobility: MobilityConfig = field(default_factory=MobilityConfig)
    network: NetworkConfig = field(default_factory=NetworkConfig)


class FLSimulation:
    def __init__(self, cfg: FLSimConfig,
                 evaluator: Optional[FuzzyEvaluator] = None,
                 run: Optional[RunConfig] = None):
        # the execution profile: engine / fused probe / overlap / async
        # axis — one RunConfig shared by all three entry points; the
        # deprecated FLSimConfig kwargs fold in behind a warning
        self.run_cfg = resolve_run(cfg, run)
        get_scheme(cfg.scheme)               # unknown schemes raise here
        self.cfg = cfg
        # a live ("clients",) mesh axis partitions the in-round client
        # axis (sharded prefix + grouped trainer); captured at
        # construction so the probe packs one sample region per shard
        self.client_mesh = pipeline.active_client_mesh()
        self.n_shards = pipeline.mesh_client_shards(self.client_mesh)
        # a mesh spanning several jax processes (launch --multihost, or a
        # real multi-host TPU slice): every process runs this same driver
        # SPMD; per-client statics materialize addressable shards only,
        # host-consumed arrays (params, round state) stay replicated
        self.multihost = mesh_is_multihost(self.client_mesh)
        if self.multihost and self.run_cfg.engine != "batched":
            raise ValueError("multi-host meshes require engine='batched'")
        if self.multihost and self.run_cfg.server == "event":
            raise ValueError("the event-driven server does not support "
                             "multi-host meshes yet")
        rng = np.random.default_rng(cfg.seed)
        images, labels = make_dataset(cfg.samples_per_class, seed=cfg.seed)
        (tr_i, tr_l), (te_i, te_l) = train_test_split(images, labels,
                                                      seed=cfg.seed)
        self.test_images = jnp.asarray(te_i)
        self.test_labels = jnp.asarray(te_l)

        parts = partition(tr_i, tr_l, cfg.partition)
        self.n = cfg.partition.n_clients
        self.groups = stack_clients(parts, batch_size=cfg.batch_size,
                                    uniform=cfg.uniform_capacity)
        self.cap = max(g.cap for g in self.groups)
        self._group_steps = [steps_per_epoch(g.cap, cfg.batch_size)
                             for g in self.groups]
        # global (C,) validity + client -> (group, group-local row) map
        self.n_valid = np.zeros(self.n, np.int32)
        self._slot = np.zeros((self.n, 2), np.int64)
        for gi, g in enumerate(self.groups):
            self.n_valid[g.client_ids] = g.n_valid
            self._slot[g.client_ids, 0] = gi
            self._slot[g.client_ids, 1] = np.arange(g.size)
        # the packed Eq. 7 probe feeds the staged selection prefix in
        # BOTH engines (it is the pipeline's loss-feature input)
        self._build_packed_probe()
        # the full dataset is the memory bill, and each engine keeps only
        # the copy it reads: host arrays back the batched engine's cohort
        # gather, device arrays feed the loop engine's per-client calls
        if self.run_cfg.engine != "batched":
            self.groups = [dataclasses.replace(g,
                                               images=jnp.asarray(g.images),
                                               labels=jnp.asarray(g.labels))
                           for g in self.groups]

        self.slowdown = rng.uniform(*cfg.slowdown_range, self.n)
        # quality proxy for the 'extreme' placement: big data + fast compute
        quality = (self.n_valid / self.n_valid.max()
                   + 1.0 / self.slowdown)
        self.mobility = FreewayMobility(
            cfg.mobility, quality_rank=np.argsort(-quality))
        self.evaluator = evaluator or FuzzyEvaluator(
            FuzzyEvaluatorConfig(e_tau=cfg.e_tau))
        self.params = init_cnn(jax.random.PRNGKey(cfg.seed), CNN_CFG)
        self.key = jax.random.PRNGKey(cfg.seed + 1)       # selection draws
        self.train_key = jax.random.PRNGKey(cfg.seed + 2)  # fold_in schedule
        # network randomness base (replaces the stateful numpy generator
        # inside the staged prefix; folded per round, split per use).
        # Folding in the simulation seed keeps NetworkConfig — a
        # jit-static — shareable across a sweep's seed axis while every
        # seed still sees its own channel realizations.
        self.net_key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.network.seed + 53), cfg.seed)
        if self.multihost:
            # host-numpy leaves: every process feeds the multi-process
            # jits identical replicated inputs (committed single-device
            # arrays would not be globally addressable)
            self.params = jax.device_get(self.params)
            self.key = np.asarray(self.key)
            self.train_key = np.asarray(self.train_key)
            self.net_key = np.asarray(self.net_key)
        self.last_mask: Optional[np.ndarray] = None        # set per round
        # lifetime per-client participation counts (selection mask hits);
        # checkpointed so budget/fairness schemes survive preemption
        self.participation = np.zeros(self.n, np.int64)
        # what the rounds did, read by the launchers
        self.counters = trace.RoundCounters()
        self.statics = self._build_statics()
        self.stage_cfg = self._build_stage_cfg()

    # -- staged-pipeline state -----------------------------------------
    def _build_statics(self) -> pipeline.RoundStatics:
        """The arrays the pure stages read — fixed for the simulation's
        lifetime (the partition, placement and hardware mix are static)."""
        f32 = jnp.float32
        ecfg = self.evaluator.cfg
        if self.multihost:
            # replicated host-numpy leaves (tiny (N,) vectors) except the
            # probe tensors, which _build_packed_probe materialized as
            # global client-sharded arrays with addressable shards only
            f32 = np.float32
            return pipeline.RoundStatics(
                x0=np.asarray(self.mobility.x0, f32),
                speeds=np.asarray(self.mobility.speeds, f32),
                jitter_phase=np.asarray(self.mobility._jitter_phase, f32),
                slowdown=np.asarray(self.slowdown, f32),
                n_valid=np.asarray(self.n_valid, f32),
                probe_images=self._probe_images,
                probe_labels=self._probe_labels,
                probe_seg=self._probe_seg,
                probe_counts=np.asarray(self._probe_counts),
                means=np.asarray(ecfg.means, f32),
                sigmas=np.asarray(ecfg.sigmas, f32),
                level_centers=np.asarray(self.evaluator.level_centers, f32))
        return pipeline.RoundStatics(
            x0=jnp.asarray(self.mobility.x0, f32),
            speeds=jnp.asarray(self.mobility.speeds, f32),
            jitter_phase=jnp.asarray(self.mobility._jitter_phase, f32),
            slowdown=jnp.asarray(self.slowdown, f32),
            n_valid=jnp.asarray(self.n_valid, f32),
            probe_images=self._probe_images,
            probe_labels=self._probe_labels,
            probe_seg=self._probe_seg,
            probe_counts=self._probe_counts,
            means=jnp.asarray(ecfg.means, f32),
            sigmas=jnp.asarray(ecfg.sigmas, f32),
            level_centers=jnp.asarray(self.evaluator.level_centers, f32))

    def _build_stage_cfg(self) -> pipeline.StageConfig:
        return self.run_cfg.to_stage_config(self.cfg, n_clients=self.n,
                                        probe_batch=self._PROBE_BATCH)

    # ------------------------------------------------------------------
    _PROBE_BATCH = 128

    def _build_packed_probe(self) -> None:
        """Pack every client's valid probe samples into one flat tensor,
        client-aligned and (when a client mesh is active) shard-regioned.

        Client membership is static across rounds (the partition never
        changes), so the packing is computed once; each round's probe is
        then a single fused forward pass.  Each client's samples are
        padded to a whole number of probe batches (sentinel rows carry
        ``seg == n``, the overflow lane), so a batch never spans two
        clients; clients are then grouped into ``n_shards`` equal-length
        contiguous regions — one per mesh shard, padded to the longest
        with sentinel batches — which makes the sample axis exactly
        partitionable over the client mesh.  Sentinel rows only ever add
        exact zeros to real clients' Eq. 7 loss lanes, so the per-client
        losses are bitwise identical for every shard count (the
        sharded-vs-single-device mask parity rests on this).  The
        alignment costs probe FLOPs — up to ``_PROBE_BATCH - 1`` sentinel
        rows per client even unsharded, vs the pre-mesh tight pack — a
        deliberate trade: the probe is one forward pass per round and
        the alignment is what keeps masks reproducible across meshes.

        ``fused_probe=True`` packs TIGHT instead: no per-client batch
        alignment, so a 45-sample Table-3 client contributes 45 probe
        rows, not 128 — on quantity-skewed fleets this halves (or
        better) the probe FLOPs, which is most of the fused fast path's
        measured CPU win (benchmarks ``prefix_fusion``).  Per-client
        losses then sum the same sample losses in a different batch
        grouping, so they can differ from the aligned pack in the last
        ulp; the selection masks are pinned bit-identical to the
        default path in tests/test_probe_fuzzy.py."""
        probe = min(self.cfg.probe_samples, self.cap)
        take = np.minimum(self.n_valid, probe).astype(np.int64)
        batch = self._PROBE_BATCH
        align = 1 if self.run_cfg.fused_probe else batch
        im_shape = self.groups[0].images.shape[2:]
        im_dtype = self.groups[0].images.dtype
        lb_dtype = self.groups[0].labels.dtype

        def shard_range(d):
            return shard_client_range(self.n, self.n_shards, d)

        # the common region length is agreed from counts alone — every
        # process computes it for ALL shards without touching sample data
        aligned = take + (-take) % align
        length = max(batch, max(
            int(sum(aligned[i] for i in shard_range(d)) or 0)
            for d in range(self.n_shards)))

        def build_region(d):
            """Shard ``d``'s probe region, padded to ``length`` with
            sentinel rows (seg == n: the overflow loss lane)."""
            ims, lbs, segs = [], [], []
            for i in shard_range(d):
                gi, li = self._slot[i]
                g = self.groups[gi]
                t = int(take[i])
                ims.append(g.images[li, :t])
                lbs.append(g.labels[li, :t])
                segs.append(np.full(t, i))
                pad = (-t) % align
                if pad:                      # align the client to batches
                    ims.append(np.zeros((pad,) + im_shape, im_dtype))
                    lbs.append(np.zeros(pad, lb_dtype))
                    segs.append(np.full(pad, self.n))
            used = int(sum(aligned[i] for i in shard_range(d)) or 0)
            pad = length - used
            ims.append(np.zeros((pad,) + im_shape, im_dtype))
            lbs.append(np.zeros(pad, lb_dtype))
            segs.append(np.full(pad, self.n))
            return (np.concatenate(ims), np.concatenate(lbs),
                    np.concatenate(segs).astype(np.int32))

        if not self.multihost:
            regions = [build_region(d) for d in range(self.n_shards)]
            self._probe_images = jnp.asarray(
                np.concatenate([r[0] for r in regions]))
            self._probe_labels = jnp.asarray(
                np.concatenate([r[1] for r in regions]))
            self._probe_seg = jnp.asarray(
                np.concatenate([r[2] for r in regions]))
        else:
            # per-host loading: each process builds ONLY the regions its
            # devices own and assembles global client-sharded arrays —
            # the (S, 28, 28, 1) probe stack never fully materializes on
            # any single host
            from jax.sharding import NamedSharding, PartitionSpec
            mesh = self.client_mesh
            cache: Dict[int, tuple] = {}

            def region(d):
                if d not in cache:
                    cache[d] = build_region(d)
                return cache[d]

            def globalize(col, extra_dims, dtype):
                shape = (self.n_shards * length,) + extra_dims
                sh = NamedSharding(
                    mesh, PartitionSpec(CLIENT_AXIS,
                                        *([None] * len(extra_dims))))

                def cb(index):
                    start = index[0].start or 0
                    return np.asarray(region(start // length)[col],
                                      dtype=dtype)

                return jax.make_array_from_callback(shape, sh, cb)

            self._probe_images = globalize(0, im_shape, im_dtype)
            self._probe_labels = globalize(1, (), lb_dtype)
            self._probe_seg = globalize(2, (), np.int32)
            cache.clear()
        self._probe_counts = jnp.asarray(take.astype(np.int32)) \
            if not self.multihost else take.astype(np.int32)

    def _round_keys(self, rnd: int) -> jax.Array:
        """Per-(round, client) PRNG keys — engine-independent, so the loop
        and batched engines train every client with identical randomness."""
        rk = jax.random.fold_in(self.train_key, rnd)
        return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            rk, jnp.arange(self.n))

    def selection_state(self, rnd: int, *,
                        elect: Optional[str] = None) -> Dict[str, jax.Array]:
        """Run the staged selection prefix (probe -> evaluate -> select ->
        deadline) for round ``rnd`` as one jitted call.  Deterministic in
        ``(params, rnd)``: the same round can be queried repeatedly.

        ``elect`` overrides the stage config's election seam for this
        call — the overflow fallback re-runs a round with
        ``elect="gather"`` (see ``resolve_elect_overflow``).

        The evaluator's membership parameters are re-read every call, so
        a post-construction ``FuzzyEvaluator.calibrate()`` takes effect
        on the next round exactly as in the host-driven engine.  (The
        sweep's vmapped path stacks statics once up front and therefore
        pins calibration at stacking time.)"""
        ecfg = self.evaluator.cfg
        arr = np.asarray if self.multihost \
            else (lambda a, d: jnp.asarray(a, d))
        st = dataclasses.replace(
            self.statics,
            means=arr(ecfg.means, np.float32),
            sigmas=arr(ecfg.sigmas, np.float32))
        cfg = self.stage_cfg
        if elect is not None and elect != cfg.elect:
            cfg = dataclasses.replace(cfg, elect=elect)
        rnd_in = np.int32(rnd) if self.multihost else jnp.int32(rnd)
        if self.client_mesh is not None:
            return pipeline.selection_prefix_sharded(
                st, self.params, rnd_in, self.key,
                self.net_key, cfg=cfg, mesh=self.client_mesh)
        return pipeline.selection_prefix(
            st, self.params, rnd_in, self.key,
            self.net_key, cfg=cfg)

    def resolve_elect_overflow(self, rnd: int, host: Dict) -> Dict:
        """The windowed election's parity escape hatch: when round
        ``rnd``'s prefix raised ``elect_overflow`` (a fixed window/halo
        buffer could not hold every dense comparison), re-run the prefix
        with the gather election and use that state instead.  The prefix
        is pure in ``(params, rnd)``, so the re-run sees identical
        inputs — the returned masks are exactly the dense election's."""
        if not _elect_overflowed(host):
            return host
        return jax.device_get(self.selection_state(rnd, elect="gather"))

    def gather_selection(self, rnd: int,
                         state: Dict[str, jax.Array]) -> Dict:
        """The cohort-gather fence of round ``rnd``: the prefix state
        read to the host, re-run dense when the windowed election
        overflowed (``resolve_elect_overflow``).  Every driver reads the
        round's selection here, under the ``fl.fence`` span."""
        with trace.span(trace.FENCE, round=rnd):
            host = jax.device_get(state)
            self.counters.rounds += 1
            if _elect_overflowed(host):
                self.counters.elect_reruns += 1
                with trace.span(trace.ELECT_RERUN, round=rnd):
                    host = self.resolve_elect_overflow(rnd, host)
        return host

    def _comm_accounting(self, n_selected: int) -> Dict[str, float]:
        """Per-round communication (bytes and time) per §4.2 / Fig. 9,
        routed through ``core/overhead.py`` so the simulator and the
        Fig. 2 / Fig. 9 analytics report consistent numbers — including
        the DUPLEX_FACTOR on state traffic and the IoVParams per-message
        latencies (cloud vs DSRC).  The accumulated-time model key comes
        from the scheme registry: ``"cfl"`` schemes maintain classical
        full state, the others exchange evaluations (cloud vs DSRC)."""
        cfg = self.cfg
        key = get_scheme(cfg.scheme).overhead_key
        state_bytes = (cfg.state_bytes if key == "cfl" else cfg.eval_bytes)
        p = IoVParams(n_participants=self.n, clients_per_round=n_selected,
                      round_period_s=cfg.deadline_s,
                      model_bytes=cfg.model_bytes,
                      state_bytes_cfl=cfg.state_bytes,
                      state_bytes_ccs_fuzzy=cfg.eval_bytes,
                      eval_bytes_dcs=cfg.eval_bytes,
                      uplink_bps_best=cfg.network.best_rate_bps,
                      uplink_bps_worst=cfg.network.worst_rate_bps)
        comm_t = accumulated_time_s(key, cfg.state_interval_s, p)
        upload_t = accumulated_time_s("model-only", cfg.state_interval_s, p)
        return {"state_bytes": state_maintenance_bytes(
                    self.n, state_bytes, cfg.deadline_s,
                    cfg.state_interval_s),
                "upload_bytes": model_upload_bytes(n_selected,
                                                   cfg.model_bytes),
                "state_time_s": comm_t - upload_t,
                "comm_time_s": comm_t}

    # -- local training + aggregation (steps 5-7) ----------------------
    def _train_loop(self, survivors: np.ndarray, keys: jax.Array):
        """Reference path: per-client jitted local_train calls + list
        FedAvg over the survivors -> the new global params.  An empty
        round is a no-op broadcast (``self.params`` itself).  Each client
        trains at its own capacity group's cap/steps, so the per-client
        math matches the grouped batched engine exactly."""
        cfg = self.cfg
        new_models, weights = [], []
        for i in np.where(survivors)[0]:
            gi, li = self._slot[i]
            g = self.groups[gi]
            p_i, _ = local_train(
                self.params, g.images[li], g.labels[li],
                jnp.int32(self.n_valid[i]), keys[i], epochs=cfg.local_epochs,
                batch_size=cfg.batch_size,
                steps_per_epoch=self._group_steps[gi], lr=cfg.lr,
                prox_mu=cfg.prox_mu)
            new_models.append(p_i)
            weights.append(float(self.n_valid[i]))
        self.counters.cohort_rows += len(new_models)
        if not new_models:
            return self.params
        return fedavg(new_models, weights)       # Eq. 2

    # cohort bucketing lives with the staged training stage now
    _bucket = staticmethod(pipeline.cohort_bucket)

    def _bucket_n(self, k: int) -> int:
        """Cohort bucket for ``k`` survivors, rounded to a mesh multiple
        when the client axis is sharded (every device gets an equal
        cohort slice)."""
        return pipeline.cohort_bucket_sharded(k, self.n_shards)

    def warmup(self, buckets=None) -> None:
        """Pre-compile the batched trainer for the given cohort bucket
        sizes in every capacity group (the jit cache persists across
        rounds).  The default covers small cohorts plus the
        central-selection budget, clipped to each group's size; a cohort
        that lands in an uncovered bucket still works — it just compiles
        on first use.  No-op for the loop engine."""
        if self.run_cfg.engine != "batched":
            return
        cfg = self.cfg
        if buckets is None:
            buckets = sorted({self._bucket_n(k) for k in
                              (2, 4, 6, 8, min(cfg.n_clients_central,
                                               self.n))})
        keys = self._round_keys(0)
        for gi, g in enumerate(self.groups):
            for b in sorted({min(b, self._bucket_n(g.size))
                             for b in buckets}):
                idx = np.zeros(b, np.int64)
                if self.client_mesh is not None:
                    pipeline.train_group_cohort_sharded(
                        self.params, g, self._group_steps[gi], idx,
                        np.zeros(b, np.float32),
                        keys[jnp.asarray(g.client_ids[idx])],
                        self.client_mesh, epochs=cfg.local_epochs,
                        batch_size=cfg.batch_size, lr=cfg.lr,
                        prox_mu=cfg.prox_mu)
                    continue
                # the donated twin is the jit the round path actually
                # calls (train_groups) — warming the plain wrapper
                # would fill a cache nobody reads; the dummy inputs
                # here are fresh, so donation is safe
                local_train_batch_donated(
                    self.params, jnp.asarray(g.images[idx]),
                    jnp.asarray(g.labels[idx]),
                    jnp.asarray(g.n_valid[idx]),
                    keys[jnp.asarray(g.client_ids[idx])],
                    epochs=cfg.local_epochs, batch_size=cfg.batch_size,
                    steps_per_epoch=self._group_steps[gi], lr=cfg.lr,
                    prox_mu=cfg.prox_mu)

    def _train_batched(self, survivors: np.ndarray, keys: jax.Array):
        """The staged ``train_groups`` + ``aggregate`` stages -> the new
        global params: one vmap(local_train) per capacity group over that
        group's surviving cohort, the mask folded into the FedAvg weights
        (Eq. 2).  Stragglers are dropped at the gather (their update is
        discarded either way; at IoV scale their local SGD FLOPs are
        not).  An empty round (or per-group cohort) is a no-op broadcast
        (``self.params`` itself).  Under a client mesh each device trains
        its shard of every group's cohort and FedAvg finishes with a
        cross-device psum."""
        cfg = self.cfg
        if self.client_mesh is not None:
            trained = pipeline.train_groups_sharded(
                self.params, self.groups, self._group_steps, survivors,
                keys, self.client_mesh, epochs=cfg.local_epochs,
                batch_size=cfg.batch_size, lr=cfg.lr, prox_mu=cfg.prox_mu,
                counters=self.counters)
            return pipeline.aggregate_sharded(self.params, trained)
        trained = pipeline.train_groups(
            self.params, self.groups, self._group_steps, survivors, keys,
            epochs=cfg.local_epochs, batch_size=cfg.batch_size, lr=cfg.lr,
            prox_mu=cfg.prox_mu, counters=self.counters)
        return pipeline.aggregate(self.params, trained)

    # ------------------------------------------------------------------
    def run_round(self, rnd: int) -> Dict[str, float]:
        """One federated round: the jitted staged prefix (steps 1-4 + 6),
        then the engine's training/aggregation (steps 5 + 7)."""
        return self.finish_round(rnd, self.selection_state(rnd))

    def finish_round(self, rnd: int,
                     state: Dict[str, jax.Array]) -> Dict[str, float]:
        """Complete round ``rnd`` from a selection-prefix output (which
        may come from a seed-vmapped sweep dispatch).  This is the single
        device->host crossing of the round — the survivor mask becomes
        concrete here, at the cohort gather."""
        host = self.gather_selection(rnd, state)
        self._dispatch_training(rnd, host)
        with trace.span(trace.DISPATCH, round=rnd):
            acc, n_test = evaluate_accuracy_async(
                self._eval_params(), self.test_images, self.test_labels,
                batch=256)
        return self._round_row(rnd, host, acc, n_test)

    def _eval_params(self):
        """Params as the accuracy evaluation consumes them: under a
        multi-host mesh the global (replicated) device arrays come back
        to the host first, so the local eval jit sees process-local
        inputs; otherwise the device params pass straight through."""
        return jax.device_get(self.params) if self.multihost \
            else self.params

    def _dispatch_training(self, rnd: int, host: Dict) -> None:
        """Steps 5 + 7 from a host-side prefix state: the cohort
        dispatch, then its commit.  Returns as soon as the work is
        enqueued — ``self.params`` becomes a device future."""
        self._commit_round(host, self._dispatch_cohort(rnd, host))

    def _dispatch_cohort(self, rnd: int, host: Dict):
        """Round keys, cohort gather and the training/aggregation
        dispatch of round ``rnd`` -> its new global params (a device
        future; ``self.params`` itself when nobody trained).  Commits
        nothing: ``self.params`` and the mask stay the previous round's
        until ``_commit_round``."""
        with trace.span(trace.COHORT, round=rnd):
            survivors = np.asarray(host["survivors"])
            keys = self._round_keys(rnd)
            if self.run_cfg.engine == "batched":
                return self._train_batched(survivors, keys)
            return self._train_loop(survivors, keys)

    def _commit_round(self, host: Dict, params) -> None:
        """Make a dispatched round's params the global model and record
        its selection mask."""
        self.params = params
        self._record_participation(host["mask"])

    def _record_participation(self, mask) -> None:
        """Track the round's selection mask and bump the lifetime
        participation counters (single bookkeeping point for the sync
        dispatch and the event server's enqueue)."""
        self.last_mask = np.asarray(mask)
        self.participation[self.last_mask > 0] += 1

    # -- preemption safety (ISSUE 10) ----------------------------------
    def capture_state(self) -> Dict:
        """The complete mutable round state, as host arrays: params, all
        PRNG bases, participation counters, the last selection mask and
        the mobility field.  Everything else the rounds read is static
        (rebuilt from ``FLSimConfig`` at construction), so restoring
        this into a freshly constructed simulation reproduces the
        uninterrupted trajectory bit-for-bit.

        The PRNG bases and mobility arrays are constants per config —
        they are captured anyway so ``restore_state`` can *verify* the
        resuming process was constructed from the same config instead of
        trusting the caller."""
        return {
            "params": jax.device_get(self.params),
            "key": np.asarray(self.key),
            "train_key": np.asarray(self.train_key),
            "net_key": np.asarray(self.net_key),
            "participation": np.asarray(self.participation),
            "last_mask": (np.asarray(self.last_mask)
                          if self.last_mask is not None
                          else np.zeros(self.n, np.float32)),
            "mobility": {
                "x0": np.asarray(self.mobility.x0, np.float64),
                "speeds": np.asarray(self.mobility.speeds, np.float64),
                "jitter_phase": np.asarray(self.mobility._jitter_phase,
                                           np.float64)},
        }

    def restore_state(self, state: Dict,
                      extra: Optional[Dict] = None) -> None:
        """Restore a ``capture_state`` snapshot.  Raises ``ValueError``
        when the snapshot demonstrably came from a different
        configuration (fleet size, seeds, mobility field)."""
        part = np.asarray(state["participation"])
        if part.shape != (self.n,):
            raise ValueError(
                f"checkpoint is for a {part.shape[0]}-client fleet; this "
                f"simulation has {self.n} clients")
        for name, cur in (("key", self.key), ("train_key", self.train_key),
                          ("net_key", self.net_key)):
            if not np.array_equal(np.asarray(state[name]), np.asarray(cur)):
                raise ValueError(
                    f"checkpoint PRNG base {name!r} does not match this "
                    f"simulation's (different seed or network config)")
        mob = state["mobility"]
        for name, cur in (("x0", self.mobility.x0),
                          ("speeds", self.mobility.speeds),
                          ("jitter_phase", self.mobility._jitter_phase)):
            if not np.array_equal(np.asarray(mob[name], np.float64),
                                  np.asarray(cur, np.float64)):
                raise ValueError(
                    f"checkpoint mobility field {name!r} does not match "
                    f"this simulation's configuration")
        conv = np.asarray if self.multihost else jnp.asarray
        self.params = jax.tree.map(conv, state["params"])
        self.participation = part.astype(np.int64)
        self.last_mask = np.asarray(state["last_mask"])
        if faults.active("overflow", "resume"):
            # chaos knob: clamp the windowed election's bucket capacity
            # so every post-resume round overflows and exercises the
            # dense-recovery path (masks stay exact by construction)
            self.stage_cfg = dataclasses.replace(self.stage_cfg,
                                                 elect_capacity=1)

    def _round_row(self, rnd: int, host: Dict, acc_count: jax.Array,
                   n_test: int) -> Dict[str, float]:
        """Resolve the round's metrics row (blocks on the accuracy
        count — the round's second and last device read).

        The async columns (active-fleet size, stale-update fraction,
        effective cohort size, rounds-behind histogram) are emitted for
        every server so the sweep CSV schema is uniform; under the
        synchronous barrier they are the degenerate values (everything
        active and on time) and the event server overrides them from its
        tick counters."""
        with trace.span(trace.READ, round=rnd):
            n_selected = int(host["n_selected"])
            survivors = np.asarray(host["survivors"])
            n_agg = int(survivors.sum())
            row = {"round": rnd,
                   "accuracy": float(acc_count) / float(n_test),
                   "n_selected": n_selected,
                   "n_aggregated": n_agg,
                   "n_straggler": int(host["n_straggler"]),
                   "n_active": int(host.get("n_active", self.n)),
                   "stale_frac": 0.0,
                   "n_effective": float(n_agg),
                   "rounds_behind_hist": f"{n_agg}/0/0/0",
                   "mean_eval_selected": float(host["mean_eval_selected"])}
            row.update(self._comm_accounting(n_selected))
        return row

    def run(self, n_rounds: Optional[int] = None,
            overlap: Optional[bool] = None, *,
            checkpointer=None,
            resume: Optional[bool] = None) -> List[Dict[str, float]]:
        """Drive ``n`` rounds; ``overlap`` defaults to the run config's
        round-ahead scheduler setting.  ``RunConfig(server="event")``
        (or any async knob) routes through the event-driven server.

        Preemption safety (ISSUE 10): with a ``checkpointer`` (or the
        run config's ``checkpoint_dir``) the complete round state is
        snapshotted every ``checkpoint_every`` rounds; ``resume``
        (default: the run config's) restores the latest good snapshot
        and continues — the finished rows, masks and params are pinned
        bit-identical to an uninterrupted run."""
        n = n_rounds or self.cfg.n_rounds
        if self.run_cfg.server == "event":
            from repro.fl.async_server import EventDrivenServer
            return EventDrivenServer(self).run(n, overlap=overlap,
                                               checkpointer=checkpointer,
                                               resume=resume)
        ckpt = build_round_checkpointer(self.run_cfg, checkpointer)
        resume = self.run_cfg.resume if resume is None else resume
        rows, start = resume_rows(self, ckpt, resume)
        if overlap is None:
            overlap = self.run_cfg.overlap_rounds
        if overlap:
            return self.run_overlapped(n, start=start, rows=rows,
                                       checkpointer=ckpt)
        lead = not self.multihost or jax.process_index() == 0
        for r in range(start, n):
            with trace.round_span(r):
                rows.append(self.run_round(r))
                checkpoint_round(self, ckpt, r, rows, lead=lead)
        return rows

    def run_overlapped(self, n_rounds: int, *, start: int = 0,
                       rows: Optional[List[Dict[str, float]]] = None,
                       checkpointer=None) -> List[Dict[str, float]]:
        """Round-ahead pipelined driver: identical rounds, pipelined
        dispatch.

        The selection prefix is pure in ``(statics, params, rnd, keys)``
        and training/aggregation only *dispatch* asynchronously, so
        round r+1's prefix can be enqueued on the ``params_{r+1}``
        device future as soon as round r's trainers are queued — before
        round r's metrics are read.  The only hard fence per round is
        the ``device_get`` at the cohort gather (survivor indices must
        be concrete to slice the fixed-shape stacks).  Round r's
        accuracy eval reads the same params as prefix r+1 and is queued
        *behind* it, so the device runs the eval while the host reads
        round r+1's fence and dispatches its cohort.  Step r therefore:

          1. fence r (the device runs eval r-1);
          2. dispatches cohort r without committing it;
          3. reads and checkpoints round r-1 — the checkpointer still
             sees round r-1's params and mask as the global state;
          4. commits round r (params, mask, participation);
          5. dispatches prefix r+1, then eval r.

        The last step reads and checkpoints its own round.  Rounds are
        bit-identical to the serial driver — the same executables on
        the same inputs, only enqueued in another order (pinned in
        tests/test_probe_fuzzy.py and tests/test_trace.py).

        Resume slots in transparently: the prefix is pure in
        ``(params, rnd)``, so the round-ahead dispatch a kill threw away
        is re-issued identically from the restored ``params`` — rounds
        ``start..n`` replay the uninterrupted schedule bit-for-bit."""
        rows = [] if rows is None else rows
        if start >= n_rounds:
            return rows
        lead = not self.multihost or jax.process_index() == 0

        def finish(rnd, host, acc, n_test):
            rows.append(self._round_row(rnd, host, acc, n_test))
            checkpoint_round(self, checkpointer, rnd, rows, lead=lead)

        state = self.selection_state(start)
        pending = None                   # the round whose eval is queued
        for r in range(start, n_rounds):
            with trace.round_span(r):
                host = self.gather_selection(r, state)
                params = self._dispatch_cohort(r, host)
                if pending is not None:
                    finish(*pending)
                self._commit_round(host, params)
                ahead = r + 1 < n_rounds     # round-ahead: r+1's prefix
                with trace.span(trace.DISPATCH, round=r, eval_round=r,
                                prefix_round=r + 1 if ahead else None):
                    if ahead:
                        state = self.selection_state(r + 1)
                        self.counters.evals_behind_prefix += 1
                    acc, n_test = evaluate_accuracy_async(
                        self._eval_params(), self.test_images,
                        self.test_labels, batch=256)
                pending = (r, host, acc, n_test)
                if not ahead:
                    finish(*pending)
        return rows
