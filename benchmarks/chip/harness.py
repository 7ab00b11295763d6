"""One run of one cell: set-up, the measured window, the check.

Set-up builds the fleet from the configuration file (the same for
every seed), warms every shape the window can reach (the cohort trainer and FedAvg
of each cohort bucket of the trainable capacity groups, the dense
election the windowed one falls back to, and the prefix, accuracy and
bookkeeping of ``warm_rounds`` whole rounds), and counts as ``setup_s``.

The window drives the program's own driver, ``FLSimulation.run`` with the
traffic mix's ``RunConfig``, for ``--seconds``.  Its only seam is the
driver's public ``checkpointer`` argument: ``due(rnd)`` is called once
per round after the round's accuracy has resolved and records the
round's completion time; it returns True once ``--seconds`` have passed,
and the ``save_round`` that follows hands over the rows and ends the
window on that completed round.  The window compiles nothing
(``compiles_in_window`` counts jax's backend compiles inside it).

After the window the check replays each sampled round through the same
compiled prefix and compares it, and the window's params and accuracy,
with the plain reference (``check.py``).
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from benchmarks.chip import cells, check, metrics, xplane
from benchmarks.chip import reference as ref

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised through the driver once the window's last round is in."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_block(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and (dev["platform"] != "tpu" or dev["count"] < chips):
        raise NoChip(f"this benchmark runs on {chips} TPU chip(s); jax "
                     f"sees {dev['count']} {dev['platform']} device(s)")
    return dev


class CompileCounter:
    """Counts jax's backend compiles and persistent-cache hits;
    ``window`` marks the measured window."""

    def __init__(self):
        self.total = 0
        self.in_window = 0
        self.window = False
        self.compile_s = 0.0
        self.cache_hits = 0

    def __call__(self, event: str, secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.total += 1
            self.compile_s += secs
            self.in_window += int(self.window)

    def event(self, event: str, **_kw) -> None:
        self.cache_hits += int(event == CACHE_HIT)


@contextlib.contextmanager
def listening(counter: CompileCounter):
    import jax
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter.event)
    try:
        yield counter
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        jax.monitoring.unregister_event_listener(counter.event)


class RoundClock:
    """The driver's ``checkpointer``: records each round's completion
    time; with ``seconds`` it closes the window on the first round that
    completes after them.  ``keep`` names rounds whose params (in and
    out) and mask are kept for the check; the first round that trained
    and the last round are always kept."""

    def __init__(self, sim, seconds: Optional[float] = None,
                 keep=(), annotate: bool = False):
        self.sim, self.seconds, self.keep = sim, seconds, set(keep)
        self.annotate = annotate      # traced: spans and masks per round
        self.t0 = time.perf_counter()
        self.times: List[float] = []
        self.masks: List[np.ndarray] = []
        self.rows: Optional[List[Dict]] = None
        self.kept: Dict[int, Dict] = {}
        self.trained: List[int] = []
        self._prev = sim.params
        self._span = self._open_span()

    def _open_span(self):
        if not self.annotate:
            return None
        import jax
        span = jax.profiler.TraceAnnotation("bench.round")
        span.__enter__()
        return span

    def due(self, rnd: int) -> bool:
        now = time.perf_counter()
        self.times.append(now)
        if self._span is not None:
            self._span.__exit__(None, None, None)
        p_out = self.sim.params
        if self.annotate:
            self.masks.append(np.asarray(self.sim.last_mask))
        trained = p_out is not self._prev
        if trained:
            self.trained.append(rnd)
        closing = self.seconds is not None and now - self.t0 >= self.seconds
        if (rnd in self.keep or closing
                or (trained and len(self.trained) == 1)):
            self.kept[rnd] = {"p_in": self._prev, "p_out": p_out,
                              "mask": np.asarray(self.sim.last_mask)}
        self._prev = p_out
        if closing:
            self._span = None
            return True
        self._span = self._open_span()
        return False

    def save_round(self, rnd: int, state, extra=None) -> None:
        self.rows = list((extra or {}).get("rows", []))
        raise WindowClosed(rnd)

    @property
    def intervals(self) -> List[float]:
        edges = [self.t0] + self.times
        return [b - a for a, b in zip(edges, edges[1:])]


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------

def trainable_groups(sim, conf: Dict) -> List[int]:
    """The capacity groups a round can train: under the Eq. 6 deadline
    only those whose fastest vehicle's local training alone fits in it."""
    s = conf["sim"]
    out = []
    for gi, g in enumerate(sim.groups):
        fastest = min(s["local_epochs"] * sim.slowdown[i] * sim.n_valid[i]
                      * conf["timing"]["b_exe_s"] / s["batch_size"]
                      for i in g.client_ids)
        if fastest <= s["deadline_s"]:
            out.append(gi)
    return out


def warm_training(sim, conf: Dict) -> List[int]:
    """Compile the cohort trainer, the cohort gather and FedAvg for every
    cohort bucket the trainable groups can reach, through the program's
    own ``train_groups`` and ``aggregate`` on a throwaway result."""
    import jax
    from repro.fl import pipeline
    cfg = sim.cfg
    keys = sim._round_keys(0)
    most = conf["warm_max_cohort"]
    buckets = []
    for gi in trainable_groups(sim, conf):
        g = sim.groups[gi]
        for k in sorted({pipeline.cohort_bucket(k)
                         for k in range(1, min(most, g.size) + 1)}):
            k = min(k, g.size)
            surv = np.zeros(sim.n, bool)
            surv[g.client_ids[:k]] = True
            out = pipeline.aggregate(sim.params, pipeline.train_groups(
                sim.params, sim.groups, sim._group_steps, surv, keys,
                epochs=cfg.local_epochs, batch_size=cfg.batch_size,
                lr=cfg.lr, prox_mu=cfg.prox_mu))
            jax.block_until_ready(out)
            buckets.append(k)
    return buckets


def build(resolved: Dict):
    """The simulation of a cell, warmed for its window."""
    import jax
    from repro.fl.rounds import FLSimulation
    conf, traffic = resolved["config"], resolved["traffic"]
    jax.config.update("jax_default_matmul_precision",
                      conf["matmul_precision"])
    t = [time.perf_counter()]
    sim = FLSimulation(resolved["family"].sim_config(conf),
                       run=cells.run_config(conf, traffic))
    t.append(time.perf_counter())
    buckets = warm_training(sim, conf)
    if sim.stage_cfg.elect == "windowed":     # the overflow fallback
        jax.block_until_ready(sim.selection_state(0, elect="gather"))
    t.append(time.perf_counter())
    clock = RoundClock(sim)
    sim.run(traffic["warm_rounds"], checkpointer=clock, resume=False)
    jax.block_until_ready(sim.params)
    t.append(time.perf_counter())
    log("set-up phases: fleet {:.3f} s, trainer and fallback {:.3f} s, "
        "warm rounds {:.3f} s".format(*(b - a for a, b in zip(t, t[1:]))))
    return sim, clock, buckets


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------

def world_of(sim, family) -> ref.World:
    """The round's inputs as host arrays, one entry per vehicle; the
    family copies its own data."""
    return ref.World(
        n_valid=np.asarray(sim.n_valid, np.int64),
        slowdown=np.asarray(sim.slowdown), x0=np.asarray(sim.mobility.x0),
        speeds=np.asarray(sim.mobility.speeds),
        jitter_phase=np.asarray(sim.mobility._jitter_phase),
        seed=int(sim.cfg.seed), net_seed=int(sim.cfg.network.seed),
        data=family.world_data(sim), family=family)


def replay(sim, clock: RoundClock, n_test: int) -> Dict[int, Dict]:
    """Re-run each kept round's prefix through the same executable, from
    the params that entered it, and hold it against the window's row.
    Returns per round the program's outputs and a ``replay`` count of
    mismatches."""
    import jax
    out = {}
    live = sim.params
    for rnd, k in sorted(clock.kept.items()):
        sim.params = k["p_in"]
        host = sim.resolve_elect_overflow(
            rnd, jax.device_get(sim.selection_state(rnd)))
        row = clock.rows[rnd]
        count = int(round(row["accuracy"] * n_test))
        surv = np.asarray(host["survivors"]).astype(bool)
        miss = (int(np.sum(np.asarray(host["mask"]) != k["mask"]))
                + int(int(host["n_selected"]) != row["n_selected"])
                + int(int(surv.sum()) != row["n_aggregated"])
                + int(int(host["n_straggler"]) != row["n_straggler"])
                + int(float(host["mean_eval_selected"])
                      != row["mean_eval_selected"]))
        out[rnd] = {"pos": np.asarray(host["pos"]),
                    "feats": np.asarray(host["feats"]),
                    "evals": np.asarray(host["evals"]),
                    "mask": np.asarray(host["mask"]), "survivors": surv,
                    "p_in": jax.device_get(k["p_in"]),
                    "params": jax.device_get(k["p_out"]),
                    "count": count, "replay": miss}
    sim.params = live
    return out


def check_rounds(world: ref.World, conf: Dict, outs: Dict[int, Dict],
                 limits: Dict[str, float]) -> Dict:
    """Every kept round's numbers, their worst, and the verdict."""
    import jax
    per = []
    with jax.default_matmul_precision("highest"):
        for rnd, o in sorted(outs.items()):
            p_in = jax.device_put(o["p_in"])
            nums = check.compare_round(world, conf, rnd, p_in, o)
            nums["replay"] = o["replay"]
            per.append(nums)
    worst = check.worst(per)
    v = check.verdict(worst, limits)
    v["failed"] = sum(not check.verdict(n, limits)["correct"] for n in per)
    v["rounds"] = sorted(outs)
    return v


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def window_work(conf: Dict, world: ref.World, clock: RoundClock) -> Dict:
    """Required work of the window's rounds, from shapes and decisions,
    as the family counts it: the probe and the accuracy read every
    round, and the local SGD of every vehicle the round trained (the
    Eq. 6 survivors of its mask)."""
    rounds = len(clock.times)
    trained: List[int] = []
    for rnd, mask in enumerate(clock.masks[:rounds]):
        _, k_up = ref.round_keys(world, conf, rnd)
        who = ref.deadline(world, conf, ref.positions(world, conf, rnd),
                           mask, k_up)
        trained.extend(np.nonzero(who)[0].tolist())
    return world.family.work(conf, world, rounds, trained)


def keep_rounds(seed: int, est: int, k: int) -> List[int]:
    """``k`` rounds drawn from the seed (a whole number >= 0) among the
    first ``est``."""
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(max(est, 1), size=min(k, max(est, 1)),
                             replace=False).tolist())


def measure(resolved: Dict, seed: int, seconds: float, trace: bool,
            root: Path = cells.ROOT) -> Dict:
    """Set-up, then the window.  Returns the live simulation, the
    window's clock, the compile counter, ``setup_s`` and, traced, the
    profile directory."""
    import jax
    traffic, name = resolved["traffic"], resolved["cell"]["name"]
    counter = CompileCounter()
    with listening(counter):
        t0 = time.perf_counter()
        sim, warm, buckets = build(resolved)
        setup_s = time.perf_counter() - t0
        warm_round = statistics.median(warm.intervals[1:] or
                                       warm.intervals)
        warm.sim = None
        log(f"{name} seed {seed}: set-up {setup_s:.3f} s, "
            f"{counter.total} compiles ({counter.compile_s:.1f} s), "
            f"{counter.cache_hits} cache hits, trainer buckets {buckets}, "
            f"warm round {1e3 * warm_round:.3f} ms")
        keep = keep_rounds(seed, int(0.8 * seconds / max(warm_round, 1e-6)),
                           traffic["check_rounds"])
        tdir = cells.out_dir(root, f"trace/{name}") if trace else None
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        counter.window = True
        with jax.profiler.TraceAnnotation("bench.window"):
            clock = RoundClock(sim, seconds, keep=keep, annotate=trace)
            try:
                sim.run(10 ** 9, checkpointer=clock, resume=False)
            except WindowClosed:
                pass
        counter.window = False
        if trace:
            jax.block_until_ready(sim.params)
            jax.profiler.stop_trace()
    agg = np.bincount([r["n_aggregated"] for r in clock.rows or []])
    log(f"window: {len(clock.times)} rounds in "
        f"{clock.times[-1] - clock.t0:.3f} s, {counter.in_window} compiles "
        f"in the window, rounds by survivors {agg.tolist()}, checked "
        f"rounds {sorted(clock.kept)}")
    return {"sim": sim, "clock": clock, "counter": counter,
            "setup_s": setup_s, "tdir": tdir, "family": resolved["family"]}


def collect(run: Dict) -> Dict:
    """After the window: the kept rounds replayed, the world on the host,
    and the program's device state let go."""
    sim, clock, fam = run["sim"], run["clock"], run["family"]
    outs = replay(sim, clock, fam.n_test(sim))
    world = world_of(sim, fam)
    clock.kept.clear()
    clock.sim = run["sim"] = None
    del sim
    gc.collect()
    return {"outs": outs, "world": world}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = cells.ROOT, require_tpu: bool = True,
             resolved: Optional[Dict] = None) -> Dict:
    """One run of ``workload``; returns the result line's object."""
    import jax
    resolved = resolved or cells.resolve(workload, root)
    conf, c = resolved["config"], resolved["cell"]
    dev = device_block(c["chips"], require_tpu)
    run = measure(resolved, seed, seconds, trace, root)
    clock = run["clock"]
    rounds = len(clock.times)
    window_s = clock.times[-1] - clock.t0
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    got = collect(run)
    t_ref = time.perf_counter()
    verdict = check_rounds(got["world"], conf, got["outs"], conf["limits"])
    log(f"check: {time.perf_counter() - t_ref:.1f} s over rounds "
        f"{verdict['rounds']}")

    metrics_out = {}
    breakdown = None
    if not trace:
        e2e = {
            "seed_rounds_per_s": rounds / window_s,
            "round_p95_ms": 1e3 * float(np.percentile(clock.intervals, 95)),
            "setup_s": run["setup_s"]}
        for m in resolved["end_to_end"]:
            metrics_out[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        tr = xplane.read(xplane.find_xplane(str(run["tdir"])))
        lo, hi = xplane.span(tr, "bench.window")
        red = xplane.reduce_window(tr, lo, hi)
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        ctx = {"window": red, "trace": tr, "lo": lo, "hi": hi,
               "rounds": rounds, "kind": dev["kind"], "chips": c["chips"],
               "counters": {"backend_compile": run["counter"].in_window},
               "work": window_work(conf, got["world"], clock)}
        for m in resolved["per_layer"]:
            v = metrics.read(m["spec"], ctx)
            if v is not None:
                metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(run["tdir"], ignore_errors=True)

    result = {"correct": verdict["correct"], "attempted": rounds,
              "failed": verdict["failed"], "metrics": metrics_out,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = verdict["compared"]
    return result
