"""Sharded multi-seed sweep over (scheme x classes-per-client x
distribution x async scenario) — the paper's Figs. 6-9 evaluation grid
with error bars, plus the event-driven fleet axis (ISSUE 6).

  PYTHONPATH=src python -m repro.launch.sweep --fast --seeds 2
  PYTHONPATH=src python -m repro.launch.sweep --fast --seeds 3 \\
      --classes 9,6,2 --distributions uniform,extreme --out grid.csv
  PYTHONPATH=src python -m repro.launch.sweep --fast --seeds 2 \\
      --churn-rates 0,0.3 --staleness-lambdas 0,1 --agg-cadences 0,30

Each **cell** is a whole (scheme, classes_per_client, distribution,
seed) simulation; the async flags add a **scenario** axis — every
(churn rate x staleness lambda x aggregation cadence) combination runs
the full cell grid through the event-driven server
(``fl/async_server.py``) and lands in the same tidy CSV with the
streaming columns (active fleet size, stale-update fraction, effective
cohort size, rounds-behind histogram).  The all-defaults scenario is
the synchronous round barrier, bit-identical to a sweep with no async
flags at all.

The harness exploits the staged round pipeline (``fl/pipeline.py``) on
two axes:

- **seeds are vmapped**: all seeds of a cell group share one
  ``StageConfig`` (the jit-static), so their selection prefixes run as a
  single ``selection_prefix_seeds`` dispatch per round — one compiled
  program evaluates S seeds' probe/evaluate/select/deadline stages at
  once.  Training still runs per seed (cohorts differ), through the same
  ``finish_round`` the single-seed drivers use.
- **cell groups are distributed**: groups are placed round-robin over
  ``repro.sharding.api.sweep_devices()`` (the active mesh's devices, or
  all local devices) via ``jax.default_device`` — this spreads *memory*
  (each group's datasets and jit executables live on its device) but
  the in-process loop is synchronous, so wall-clock parallelism comes
  from worker *processes* (``--workers N``, spawn-based).  On a single
  CPU device with one worker this degrades to serial execution — the
  correctness baseline.
- **the client axis is meshed** (``--mesh clients=K``): inside the
  activated clients mesh every cell's *in-round* client axis is
  partitioned across the K devices — the seed-vmapped prefix dispatches
  as ``selection_prefix_seeds_sharded`` and the grouped trainer psums
  its FedAvg across shards.  The whole mesh is then ONE placement
  domain (``sweep_devices`` collapses to a single entry), and worker
  processes each rebuild the same mesh from the spec.

Execution knobs (engine, fused probe, overlap, mesh, server/churn/
staleness/cadence) all live on ONE ``RunConfig``
(``fl/runconfig.py``) shared with ``FLSimulation`` and
``launch/fl_sim.py`` — the scenario axis is just
``dataclasses.replace`` over that config.

Output: ONE tidy CSV, one row per (cell, scenario, round), with
per-seed metrics plus mean +/- std columns aggregated across the
group's seeds (constant within a (round, scheme, classes, distribution,
scenario) group) — directly plottable as the error-bar curves of
Figs. 6-8.  Byte/time columns come from the
``core/overhead.py``-reconciled accounting (Fig. 9).  Rows are emitted
in a deterministic order and with fixed float formatting, so a repeated
sweep is bitwise identical (tests/test_sweep.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl import pipeline, trace
from repro.fl.async_server import EventDrivenServer
from repro.fl.client import evaluate_accuracy_async
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig, add_run_arguments
from repro.fl.trace import RoundCounters
from repro.ioutil import write_atomic
from repro.launch import faults
from repro.sharding.api import sweep_devices

SCHEMES = ("dcs", "ccs-fuzzy", "random")

# one row per (cell, scenario, round): cell identity + the async
# scenario coordinates + per-seed metrics + the across-seed aggregates
# (constant within a seed group).  agg_cadence_s reports 0 for "round
# period" (RunConfig's None) so the column stays numeric.
CSV_COLUMNS = (
    "round", "scheme", "seed", "classes_per_client", "distribution",
    "churn_rate", "staleness_lambda", "agg_cadence_s",
    "accuracy", "n_selected", "n_aggregated", "n_straggler",
    "n_active", "stale_frac", "n_effective", "rounds_behind_hist",
    "mean_eval_selected", "state_bytes", "upload_bytes", "state_time_s",
    "comm_time_s",
    "accuracy_mean", "accuracy_std", "n_selected_mean", "n_selected_std",
    "n_straggler_mean", "n_straggler_std",
)

_FMT = {"accuracy": "{:.6f}", "mean_eval_selected": "{:.4f}",
        "churn_rate": "{:.3f}", "staleness_lambda": "{:.4g}",
        "agg_cadence_s": "{:.6g}",
        "stale_frac": "{:.4f}", "n_effective": "{:.4f}",
        "state_bytes": "{:.6g}", "upload_bytes": "{:.6g}",
        "state_time_s": "{:.6g}", "comm_time_s": "{:.6g}",
        "accuracy_mean": "{:.6f}", "accuracy_std": "{:.6f}",
        "n_selected_mean": "{:.4f}", "n_selected_std": "{:.4f}",
        "n_straggler_mean": "{:.4f}", "n_straggler_std": "{:.4f}"}

# the key that identifies one seed group in the tidy output: a cell
# plus its async scenario coordinates
_GROUP_KEY = ("round", "scheme", "classes_per_client", "distribution",
              "churn_rate", "staleness_lambda", "agg_cadence_s")

# sweep cell group: every seed of one (scheme, classes, distribution)
Group = Tuple[str, int, str]


def fast_cell_config(scheme: str, classes_per_client: int,
                     distribution: str, seed: int) -> FLSimConfig:
    """CPU-budget profile per cell (mirrors launch/fl_sim.fast_config).

    Fewer classes/client concentrate per-class demand under the no-dup
    partition rule, so the source pool grows with non-iid-ness."""
    part = PartitionConfig(big_quantity=300, small_quantity=45,
                           classes_per_client=classes_per_client, seed=seed)
    return FLSimConfig(
        scheme=scheme, partition=part, local_epochs=1,
        samples_per_class=600 + (9 - classes_per_client) * 80,
        mobility=MobilityConfig(distribution=distribution, seed=seed),
        seed=seed)


def paper_cell_config(scheme: str, classes_per_client: int,
                      distribution: str, seed: int) -> FLSimConfig:
    """Table 3 profile (expensive on CPU)."""
    part = PartitionConfig(classes_per_client=classes_per_client, seed=seed)
    return FLSimConfig(
        scheme=scheme, partition=part, local_epochs=30, deadline_s=20.0,
        mobility=MobilityConfig(distribution=distribution, seed=seed),
        seed=seed)


ConfigFn = Callable[[str, int, str, int], FLSimConfig]


def run_seed_group(scheme: str, classes_per_client: int, distribution: str,
                   seeds: Sequence[int], rounds: int,
                   cfg_fn: ConfigFn = fast_cell_config,
                   vmap_prefix: bool = True,
                   overlap: Optional[bool] = None,
                   run: Optional[RunConfig] = None,
                   checkpoint_dir: Optional[str] = None,
                   checkpoint_every: int = 1,
                   resume: bool = False,
                   counters: Optional[RoundCounters] = None) -> List[Dict]:
    """Run every seed of one cell group for ``rounds`` rounds.

    ``run`` is the shared execution profile (``RunConfig``): the sync
    drivers complete each round through ``FLSimulation``; any async knob
    routes training and aggregation through the cell's
    ``EventDrivenServer`` instead — the seed-vmapped prefix dispatch is
    identical either way (the event axis only changes what happens after
    the cohort gather).

    When the seeds share a ``StageConfig`` (they do by construction —
    only arrays differ), their selection prefixes are evaluated in ONE
    vmapped dispatch per round; per-seed training and aggregation then
    complete each round through the driver's ``finish_round``.

    ``overlap`` (default: the run config's ``overlap_rounds``) is the
    round-ahead scheduler: the prefix is pure in ``(statics, params,
    rnd, keys)`` and the per-seed params become device futures the
    moment the trainers are enqueued, so round r+1's (vmapped)
    selection dispatch is issued right after round r's training —
    before round r's accuracy metrics are read.  The vmapped dispatch
    then runs with ``donate_argnums`` on the seed-stacked params (a
    fresh (S, ...) stack every round).  Rows are bit-identical to the
    serial schedule — same ops, same order, earlier enqueue.

    Preemption safety (ISSUE 10): with ``checkpoint_dir`` the whole seed
    group snapshots atomically every ``checkpoint_every`` rounds (every
    seed's driver state in one ``RoundCheckpointer`` entry, plus the
    rows emitted so far); ``resume=True`` restores the latest good
    snapshot so a killed group replays only its unfinished rounds —
    bit-identically.

    ``counters`` (a ``RoundCounters``), when given, gains every seed's
    round counters."""
    run = (run if run is not None else RunConfig()).resolved()
    if overlap is None:
        overlap = run.overlap_rounds
    sims = [FLSimulation(cfg_fn(scheme, classes_per_client, distribution,
                                seed), run=run) for seed in seeds]
    if not sims:
        return []
    drivers = [EventDrivenServer(sim) if run.server == "event" else sim
               for sim in sims]
    cfg0 = sims[0].stage_cfg
    use_vmap = (vmap_prefix and len(sims) > 1
                and all(s.stage_cfg == cfg0 for s in sims))
    stacked_st = (pipeline.stack_statics([s.statics for s in sims])
                  if use_vmap else None)
    sel_keys = jnp.stack([s.key for s in sims])
    net_keys = jnp.stack([s.net_key for s in sims])
    mesh = pipeline.active_client_mesh()

    def dispatch(r: int) -> List[Dict]:
        """Enqueue round ``r``'s selection prefixes; returns per-seed
        state dicts (device futures — nothing blocks here)."""
        if not use_vmap:
            return [sim.selection_state(r) for sim in sims]
        params = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[s.params for s in sims])
        if mesh is not None:
            outs = pipeline.selection_prefix_seeds_sharded(
                stacked_st, params, jnp.int32(r), sel_keys, net_keys,
                cfg=cfg0, mesh=mesh)
        else:
            outs = pipeline.selection_prefix_seeds_donated(
                stacked_st, params, jnp.int32(r), sel_keys, net_keys,
                cfg=cfg0)
        return [jax.tree.map(lambda x, i=i: x[i], outs)
                for i in range(len(sims))]

    def meta(seed: int, row: Dict) -> Dict:
        return {"scheme": scheme, "seed": seed,
                "classes_per_client": classes_per_client,
                "distribution": distribution,
                "churn_rate": run.churn_rate,
                "staleness_lambda": run.staleness_lambda,
                "agg_cadence_s": (run.agg_cadence_s
                                  if run.agg_cadence_s is not None
                                  else 0.0),
                **row}

    ckpt = None
    if checkpoint_dir:
        from repro.train.checkpoint import RoundCheckpointer
        ckpt = RoundCheckpointer(checkpoint_dir, every=checkpoint_every)
    rows: List[Dict] = []
    start = 0
    if resume and ckpt is not None:
        got = ckpt.latest_good()
        if got is not None:
            rnd, state, extra = got
            for drv, st in zip(drivers, state["seeds"]):
                drv.restore_state(st, extra)
            rows = [dict(row) for row in extra.get("rows", [])]
            start = rnd + 1
    lead = jax.process_index() == 0
    states = None
    for r in range(start, rounds):
        with trace.round_span(r):
            if states is None:
                states = dispatch(r)
            nxt = None
            if overlap:
                # the fence also surfaces elect_overflow: any flagged seed
                # re-runs its prefix through the dense gather before
                # training, keeping windowed masks bit-identical
                hosts = [sim.gather_selection(r, s)
                         for sim, s in zip(sims, states)]
                for drv, host in zip(drivers, hosts):    # train dispatch
                    drv._dispatch_training(r, host)
                ahead = r + 1 < rounds
                with trace.span(trace.DISPATCH, round=r,
                                prefix_round=r + 1 if ahead else None):
                    pend = [evaluate_accuracy_async(sim._eval_params(),
                                                    sim.test_images,
                                                    sim.test_labels,
                                                    batch=256)
                            for sim in sims]
                    if ahead:                            # round-ahead
                        nxt = dispatch(r + 1)
                for seed, drv, host, (acc, nt) in zip(seeds, drivers, hosts,
                                                      pend):
                    rows.append(meta(seed, drv._round_row(r, host, acc,
                                                          nt)))
            else:
                for seed, drv, state in zip(seeds, drivers, states):
                    rows.append(meta(seed, drv.finish_round(r, state)))
            states = nxt
            if ckpt is not None and lead and ckpt.due(r):
                with trace.span(trace.CHECKPOINT, round=r):
                    ckpt.save_round(
                        r, {"seeds": [drv.capture_state()
                                      for drv in drivers]},
                        extra={"rows": rows, "next_round": r + 1})
                faults.fire("checkpoint-saved", round=r)
            faults.fire("round-done", round=r)
    if counters is not None:
        for sim in sims:
            counters.add(sim.counters)
    return rows


def aggregate_rows(rows: List[Dict]) -> List[Dict]:
    """Attach across-seed mean/std columns to every per-seed row (tidy:
    the aggregate is repeated within its (round, scheme, classes,
    distribution, scenario) group)."""
    groups: Dict[Tuple, List[Dict]] = {}
    for row in rows:
        # .get: rows from older callers may lack the scenario columns
        key = tuple(row.get(k) for k in _GROUP_KEY)
        groups.setdefault(key, []).append(row)
    out = []
    for row in rows:
        grp = groups[tuple(row.get(k) for k in _GROUP_KEY)]
        agg = {}
        for metric in ("accuracy", "n_selected", "n_straggler"):
            vals = np.asarray([g[metric] for g in grp], np.float64)
            agg[f"{metric}_mean"] = float(vals.mean())
            # sample std (ddof=1): the 2-3 seeds CI runs are a sample of
            # the seed distribution, and ddof=0 would understate the
            # error bars by ~30% at n=2
            agg[f"{metric}_std"] = float(vals.std(ddof=1)) \
                if len(vals) > 1 else 0.0
        out.append({**row, **agg})
    return out


def rows_to_csv(rows: List[Dict]) -> str:
    """Deterministic tidy CSV: fixed column order, fixed float formats,
    rows sorted by (scheme, classes, distribution, scenario, seed,
    round)."""
    buf = io.StringIO()
    buf.write(",".join(CSV_COLUMNS) + "\n")
    for row in sorted(rows, key=lambda r: (
            r["scheme"], r["classes_per_client"], r["distribution"],
            r["churn_rate"], r["staleness_lambda"], r["agg_cadence_s"],
            r["seed"], r["round"])):
        cells = []
        for col in CSV_COLUMNS:
            v = row[col]
            cells.append(_FMT[col].format(v) if col in _FMT else str(v))
        buf.write(",".join(cells) + "\n")
    return buf.getvalue()


# typed CSV parse: the resume path reads the sweep's own output back
_INT_COLS = {"round", "seed", "classes_per_client", "n_selected",
             "n_aggregated", "n_straggler", "n_active"}
_STR_COLS = {"scheme", "distribution", "rounds_behind_hist"}


def parse_csv_rows(text: str) -> Optional[List[Dict]]:
    """Parse a ``rows_to_csv`` artifact back into typed rows.

    Returns ``None`` when the header is not this sweep's schema (a
    foreign or incompatible file — the caller warns and starts fresh).
    Rows that fail to parse (a torn tail from a non-atomic writer, short
    or malformed lines) are dropped with a warning: their group simply
    reruns.  Because every float column re-formats idempotently under
    ``_FMT`` (parse(format(x)) == parse-stable), rows that survive a
    parse round-trip re-emit byte-identically."""
    import warnings
    lines = text.splitlines()
    if not lines or lines[0] != ",".join(CSV_COLUMNS):
        return None
    rows: List[Dict] = []
    dropped = 0
    for ln in lines[1:]:
        if not ln:
            continue
        cells = ln.split(",")
        if len(cells) != len(CSV_COLUMNS):
            dropped += 1
            continue
        try:
            row: Dict = {}
            for col, cell in zip(CSV_COLUMNS, cells):
                if col in _STR_COLS:
                    row[col] = cell
                elif col in _INT_COLS:
                    row[col] = int(cell)
                else:
                    row[col] = float(cell)
        except ValueError:
            dropped += 1
            continue
        rows.append(row)
    if dropped:
        warnings.warn(f"dropped {dropped} unparsable row(s) from the "
                      f"partial sweep CSV (torn tail); their groups "
                      f"will rerun", RuntimeWarning)
    return rows


def _scenario_key(run: RunConfig) -> Tuple[str, str, str]:
    """The async scenario coordinates as their *formatted* CSV strings —
    comparing formatted values makes job-vs-CSV matching immune to float
    parse/format wobble."""
    return (_FMT["churn_rate"].format(run.churn_rate),
            _FMT["staleness_lambda"].format(run.staleness_lambda),
            _FMT["agg_cadence_s"].format(run.agg_cadence_s
                                         if run.agg_cadence_s is not None
                                         else 0.0))


def _job_key(scheme: str, classes: int, dist: str,
             run: RunConfig) -> Tuple:
    return (scheme, int(classes), dist) + _scenario_key(run)


def _row_job_key(row: Dict) -> Tuple:
    return (row["scheme"], int(row["classes_per_client"]),
            row["distribution"],
            _FMT["churn_rate"].format(row["churn_rate"]),
            _FMT["staleness_lambda"].format(row["staleness_lambda"]),
            _FMT["agg_cadence_s"].format(row["agg_cadence_s"]))


def _group_ckpt_dir(checkpoint_dir: str, scheme: str, classes: int,
                    dist: str, run: RunConfig) -> str:
    """A deterministic per-(cell, scenario) checkpoint subdirectory —
    stable across the killed run and its resume."""
    import os
    slug = "_".join(str(p) for p in
                    _job_key(scheme, classes, dist, run)).replace(".", "p")
    return os.path.join(checkpoint_dir, slug)


def completed_job_rows(parsed: Optional[List[Dict]],
                       jobs: Sequence[Tuple[Group, RunConfig]],
                       seeds: Sequence[int],
                       rounds: int) -> Dict[Tuple, List[Dict]]:
    """Map each fully completed job (every (seed, round) row present in
    the partial CSV) to its parsed rows — those groups are skipped on
    resume and their rows pass through to the final CSV verbatim."""
    if not parsed:
        return {}
    by_job: Dict[Tuple, List[Dict]] = {}
    for row in parsed:
        by_job.setdefault(_row_job_key(row), []).append(row)
    want = {(int(s), r) for s in seeds for r in range(rounds)}
    out: Dict[Tuple, List[Dict]] = {}
    for (group, run) in jobs:
        key = _job_key(*group, run)
        got = [row for row in by_job.get(key, [])
               if (row["seed"], row["round"]) in want]
        if {(row["seed"], row["round"]) for row in got} >= want:
            out[key] = got
    return out


def _run_group_worker(args: Tuple) -> Tuple[List[Dict], RoundCounters]:
    """Top-level (picklable) worker: one cell group, serial in-process;
    returns its rows and round counters.  ``mesh_spec`` (a ``--mesh``
    string; Mesh objects don't pickle) rebuilds the client mesh inside
    the worker's own jax runtime; the frozen ``RunConfig`` pickles by
    value."""
    scheme, classes, dist, seeds, rounds, cfg_fn, vmap_prefix, \
        mesh_spec, overlap, run, cache_dir, ckpt_dir, ckpt_every, \
        resume = args
    from repro.launch.cache import enable_jit_cache
    from repro.launch.mesh import client_mesh_context
    with client_mesh_context(mesh_spec):
        # sibling workers retrace identical executables; the shared
        # persistent cache lets one worker's compile serve the rest
        enable_jit_cache(cache_dir)
        counters = RoundCounters()
        rows = run_seed_group(scheme, classes, dist, seeds, rounds,
                              cfg_fn=cfg_fn, vmap_prefix=vmap_prefix,
                              overlap=overlap, run=run,
                              checkpoint_dir=ckpt_dir,
                              checkpoint_every=ckpt_every, resume=resume,
                              counters=counters)
        return rows, counters


def sweep(schemes: Sequence[str], classes_list: Sequence[int],
          distributions: Sequence[str], seeds: Sequence[int], rounds: int,
          cfg_fn: ConfigFn = fast_cell_config, vmap_prefix: bool = True,
          workers: int = 1, mesh_spec: Optional[str] = None,
          overlap: Optional[bool] = None,
          runs: Optional[Sequence[RunConfig]] = None,
          cache_dir: Optional[str] = None,
          log: Optional[Callable[[str], None]] = None,
          out_path: Optional[str] = None,
          checkpoint_dir: Optional[str] = None,
          checkpoint_every: int = 1,
          resume: bool = False,
          counters: Optional[RoundCounters] = None) -> List[Dict]:
    """Run the full grid — every cell under every async scenario — and
    return aggregated tidy rows.

    ``runs`` is the scenario axis: one ``RunConfig`` per (churn rate x
    staleness lambda x aggregation cadence) combination (default: the
    single all-defaults sync scenario).  Cell-x-scenario groups are
    placed round-robin over ``sweep_devices()`` (serial fallback on one
    device; a clients mesh is one placement domain); ``workers > 1``
    additionally fans groups out over spawn-based processes (each worker
    owns its device runtime, so the device placement is left to the
    workers; ``cfg_fn`` crosses the process boundary by reference, so it
    must be a module-level function — a closure fails loudly at
    submission, never silently switching profiles).  ``mesh_spec``
    crosses as the ``--mesh`` string and is activated inside each worker
    (the parent's forced-device env is inherited by the spawned
    children).

    Preemption safety (ISSUE 10): with ``checkpoint_dir`` each group
    snapshots per round under its own subdirectory and — when
    ``out_path`` is set — the partial grid CSV is atomically rewritten
    after every finished group.  ``resume=True`` reads ``out_path``
    back: fully completed (cell, scenario) groups are recognized from
    their rows and skipped (their rows pass through verbatim; the
    ``_FMT`` formats are parse/format idempotent, so they re-emit
    byte-identically), in-flight groups restart from their round
    checkpoints, and the final CSV is byte-identical to an
    uninterrupted run's.

    ``counters`` (a ``RoundCounters``), when given, gains the round
    counters of every group run."""
    counters = counters if counters is not None else RoundCounters()
    log = log or (lambda s: None)
    runs = tuple(runs) if runs else (RunConfig().resolved(),)
    jobs: List[Tuple[Group, RunConfig]] = [
        ((s, c, d), run) for run in runs for s in schemes
        for c in classes_list for d in distributions]

    done: Dict[Tuple, List[Dict]] = {}
    if resume and out_path:
        import os
        if os.path.exists(out_path):
            parsed = parse_csv_rows(open(out_path).read())
            if parsed is None:
                import warnings
                warnings.warn(
                    f"{out_path} is not a sweep CSV of this schema — "
                    f"ignoring it and rerunning the full grid",
                    RuntimeWarning)
            else:
                done = completed_job_rows(parsed, jobs, seeds, rounds)
    done_rows = [row for got in done.values() for row in got]
    lead = jax.process_index() == 0

    def group_dir(scheme, classes, dist, run):
        if not checkpoint_dir:
            return None
        return _group_ckpt_dir(checkpoint_dir, scheme, classes, dist, run)

    def clear_group_ckpt(scheme, classes, dist, run):
        d = group_dir(scheme, classes, dist, run)
        if d is not None and lead:
            from repro.train.checkpoint import RoundCheckpointer
            RoundCheckpointer(d).clear()

    def finish_group(index, group, run, fresh_rows):
        """After each completed group: atomically rewrite the partial
        grid CSV (the group's rows become durable), drop its
        now-redundant round checkpoints, then announce the chaos hook.
        A kill anywhere in this sequence resumes cleanly — worst case
        (before the CSV lands) the group reruns from its checkpoints."""
        if out_path and lead:
            write_atomic(out_path,
                         rows_to_csv(aggregate_rows(fresh_rows)
                                     + done_rows))
        clear_group_ckpt(*group, run)
        faults.fire("group-done", index=index)

    todo = [(i, group, run) for i, (group, run) in enumerate(jobs)
            if _job_key(*group, run) not in done]
    for key in done:
        log(f"[sweep] resume: skipping completed group "
            f"{'/'.join(str(p) for p in key)}")
    # a completed group's checkpoints are stale — drop them so a later
    # corruption there can never shadow the CSV's finished rows
    for i, (group, run) in enumerate(jobs):
        if _job_key(*group, run) in done:
            clear_group_ckpt(*group, run)

    rows: List[Dict] = []
    if workers > 1:
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor
        work = [(s, c, d, tuple(seeds), rounds, cfg_fn, vmap_prefix,
                 mesh_spec, overlap, run, cache_dir,
                 group_dir(s, c, d, run), checkpoint_every, resume)
                for _, (s, c, d), run in todo]
        with ProcessPoolExecutor(
                max_workers=workers,
                mp_context=mp.get_context("spawn")) as pool:
            for (i, (s, c, d), run), (got, got_counters) in zip(
                    todo, pool.map(_run_group_worker, work)):
                counters.add(got_counters)
                log(f"[sweep] {s} classes={c} {d} "
                    f"churn={run.churn_rate} lam={run.staleness_lambda}: "
                    f"{len(got)} rows")
                rows.extend(got)
                finish_group(i, (s, c, d), run, rows)
        return aggregate_rows(rows) + done_rows

    devices = sweep_devices()
    for i, (scheme, classes, dist), run in todo:
        dev = devices[i % len(devices)]
        t0 = time.time()
        with jax.default_device(dev):
            got = run_seed_group(scheme, classes, dist, seeds, rounds,
                                 cfg_fn=cfg_fn, vmap_prefix=vmap_prefix,
                                 overlap=overlap, run=run,
                                 checkpoint_dir=group_dir(scheme, classes,
                                                          dist, run),
                                 checkpoint_every=checkpoint_every,
                                 resume=resume, counters=counters)
        rows.extend(got)
        finish_group(i, (scheme, classes, dist), run, rows)
        accs = [r["accuracy"] for r in got if r["round"] == rounds - 1]
        log(f"[sweep] {scheme} classes={classes} {dist} "
            f"churn={run.churn_rate} lam={run.staleness_lambda} "
            f"cadence={run.agg_cadence_s or 0} on {dev}: "
            f"final acc {np.mean(accs):.3f} +/- {np.std(accs):.3f} "
            f"({len(seeds)} seeds, {time.time() - t0:.0f}s)")
    return aggregate_rows(rows) + done_rows


def scenario_runs(base: RunConfig, churn_rates: Sequence[float],
                  staleness_lambdas: Sequence[float],
                  agg_cadences: Sequence[float]) -> List[RunConfig]:
    """The async scenario axis: every (churn x lambda x cadence) combo
    as a ``RunConfig`` derived from ``base``.  A lambda of 0 keeps the
    hard-deadline "drop" policy (weighting with lambda=0 would train
    stragglers at full weight — a different policy than the sync
    baseline); cadence 0 means "the round period"."""
    out = []
    for churn in churn_rates:
        for lam in staleness_lambdas:
            for cad in agg_cadences:
                out.append(dataclasses.replace(
                    base, churn_rate=churn,
                    staleness="weighted" if lam > 0 else base.staleness,
                    staleness_lambda=lam,
                    agg_cadence_s=cad if cad > 0 else None).resolved())
    return out


def _float_list(text: str) -> Tuple[float, ...]:
    return tuple(float(x) for x in text.split(","))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--schemes", default="all",
                    help="comma list or 'all' (dcs,ccs-fuzzy,random)")
    ap.add_argument("--classes", default="9",
                    help="comma list of classes-per-client (Fig. 8: 9,6,2)")
    ap.add_argument("--distributions", default="uniform",
                    help="comma list (Fig. 7: uniform,extreme)")
    ap.add_argument("--seeds", type=int, default=2,
                    help="number of seeds per cell (0..N-1)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--fast", action="store_true",
                    help="CPU-budget profile (the default)")
    ap.add_argument("--paper-profile", action="store_true",
                    help="Table 3 profile (expensive on CPU)")
    ap.add_argument("--workers", type=int, default=1,
                    help="worker processes for cell groups (1 = "
                         "in-process; more only on the CPU backend)")
    ap.add_argument("--no-vmap", action="store_true",
                    help="disable the seed-vmapped selection prefix")
    # the shared RunConfig flags (mesh / fused probe / overlap / server /
    # single-scenario async knobs) — fl/runconfig.py
    add_run_arguments(ap)
    # the *plural* scenario-axis flags: each adds a grid dimension
    ap.add_argument("--churn-rates", type=_float_list, default=None,
                    help="comma list of coverage-window churn rates "
                         "(scenario axis; e.g. 0,0.3)")
    ap.add_argument("--staleness-lambdas", type=_float_list, default=None,
                    help="comma list of staleness decay lambdas "
                         "(scenario axis; 0 = hard-deadline drop)")
    ap.add_argument("--agg-cadences", type=_float_list, default=None,
                    help="comma list of aggregation cadences in simulated "
                         "seconds (scenario axis; 0 = the round period)")
    from repro.launch.cache import add_cache_arguments, resolve_cache_dir
    from repro.launch.multihost import (add_multihost_arguments,
                                        multihost_from_args,
                                        require_cpu_backend, should_spawn,
                                        spawn_multihost)
    add_multihost_arguments(ap)
    add_cache_arguments(ap)
    ap.add_argument("--out", default="sweep.csv")
    args = ap.parse_args(argv)

    # checkpoints default to a dotdir beside the output (mirrors the jit
    # cache); set BEFORE RunConfig.from_args so --resume validates
    if args.checkpoint_dir is None:
        args.checkpoint_dir = args.out + ".ckpt"

    if args.fast and args.paper_profile:
        ap.error("--fast and --paper-profile are mutually exclusive")
    if args.multihost > 1 and args.workers > 1:
        ap.error("--multihost and --workers are mutually exclusive (a "
                 "multi-process mesh is already one placement domain)")
    if should_spawn(args):
        import sys
        return spawn_multihost("repro.launch.sweep",
                               list(argv) if argv is not None
                               else sys.argv[1:], args.multihost)
    if args.seeds < 1:
        ap.error("--seeds must be >= 1")
    if args.rounds < 1:
        ap.error("--rounds must be >= 1")
    schemes = SCHEMES if args.schemes == "all" \
        else tuple(args.schemes.split(","))
    for s in schemes:
        if s not in SCHEMES:
            ap.error(f"unknown scheme {s!r} (known: {SCHEMES})")
    classes_list = tuple(int(c) for c in args.classes.split(","))
    distributions = tuple(args.distributions.split(","))
    cfg_fn = paper_cell_config if args.paper_profile else fast_cell_config

    full_run = RunConfig.from_args(args)
    # the grid drives rounds itself — per-group checkpointing is the
    # sweep's own (run_seed_group), not the per-sim RunConfig contract
    base_run = dataclasses.replace(full_run, checkpoint_dir=None,
                                   checkpoint_every=1, resume=False)
    if (args.churn_rates is None and args.staleness_lambdas is None
            and args.agg_cadences is None):
        runs = [base_run]
    else:
        runs = scenario_runs(base_run,
                             args.churn_rates or (base_run.churn_rate,),
                             args.staleness_lambdas
                             or (base_run.staleness_lambda,),
                             args.agg_cadences
                             or (base_run.agg_cadence_s or 0.0,))

    t0 = time.time()
    counters = RoundCounters()
    cache_dir = resolve_cache_dir(args.jit_cache_dir)
    from repro.launch.cache import enable_jit_cache
    from repro.launch.mesh import client_mesh_context
    with client_mesh_context(args.mesh,
                             multihost=multihost_from_args(args)) as mesh:
        is_lead = jax.process_index() == 0
        if args.workers > 1:
            require_cpu_backend(f"--workers {args.workers}")
        else:
            enable_jit_cache(cache_dir)   # workers enable their own
        if mesh is not None and is_lead:
            print(f"[sweep] client mesh: {dict(mesh.shape)} over "
                  f"{mesh.devices.size} devices"
                  + (f" / {jax.process_count()} processes"
                     if jax.process_count() > 1 else ""), flush=True)
        rows = sweep(schemes, classes_list, distributions,
                     seeds=range(args.seeds), rounds=args.rounds,
                     cfg_fn=cfg_fn, vmap_prefix=not args.no_vmap,
                     workers=args.workers, mesh_spec=args.mesh,
                     runs=runs, cache_dir=cache_dir,
                     log=(lambda s: print(s, flush=True)) if is_lead
                     else (lambda s: None),
                     out_path=args.out,
                     checkpoint_dir=full_run.checkpoint_dir,
                     checkpoint_every=full_run.checkpoint_every,
                     resume=full_run.resume, counters=counters)
    csv_text = rows_to_csv(rows)
    if is_lead:                  # one writer in a multi-process launch
        write_atomic(args.out, csv_text)
        print(f"[sweep] wrote {len(rows)} rows "
              f"({len(schemes)}x{len(classes_list)}x{len(distributions)} "
              f"cells x {len(runs)} scenarios x {args.seeds} seeds x "
              f"{args.rounds} rounds) to {args.out} in "
              f"{time.time() - t0:.0f}s; {counters}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
