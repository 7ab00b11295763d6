"""Per-layer readers of the program's own spans, scopes and counters.

The program writes ``fl.*`` host spans (``repro.fl.trace``) into the
profiler's trace, names its prefix stages with ``jax.named_scope`` and
keeps ``RoundCounters`` on the simulation.  The readers here turn them
into per-layer metrics, with the same ``(spec, ctx)`` signature as
``metrics.py``'s and registered in ``metrics.KINDS`` on import:

- ``span_idle_ms_per_round``: device idle time (``xplane.gaps``) that
  falls inside the host intervals of the named spans, ms per round,
  averaged over devices;
- ``scope_ms_per_round``: device ms per round of the ops, inside the
  named executables, whose scope path holds ``/<scope>/``; the context
  gives each device's ``(start, end, path)`` op events (``op_paths``);
- ``counter_share``: 100 x one counter / a sum of counters.

Each returns None when nothing in the trace or the counters matches.

``python3 benchmarks/chip/spans.py --workload <cell> --seed <n>
--seconds <s>`` runs one traced window of a cell (``harness.measure``)
and prints, as the last line, one JSON object: the cell's device-trace
per-layer metrics (but the two that need the check's required work),
the metrics of ``SPAN_METRICS``, the prefix's time per stage scope, the
idle split by span and the top device ops by executable and scope.  The
counters are the simulation's over its warm-up rounds and the window.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):                # run as a script
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import metrics, xplane  # noqa: E402

SPAN_PREFIX = "fl."
# metric files this script reads beside the cell's own
SPAN_METRICS = ("fedavg_device_ms", "probe_device_ms", "elect_device_ms",
                "fence_idle_ms", "cohort_idle_ms", "elect_rerun_share",
                "train_pad_share")
PREFIX_MODULES = ("selection_prefix", "_prefix_seeds_body")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")

Interval = Tuple[float, float]


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def intersect_ns(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Nanoseconds common to two sorted lists of disjoint intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def host_intervals(trace: xplane.Trace, names: Sequence[str], lo: float,
                   hi: float) -> List[Interval]:
    """The merged host intervals of the spans called any of ``names``."""
    evs = [(s, e, n) for s, e, n, _ in trace.host if n in names]
    return xplane.union(xplane.clip(evs, lo, hi))


def span_idle_ns(trace: xplane.Trace, names: Sequence[str], lo: float,
                 hi: float) -> Optional[float]:
    """Device idle nanoseconds inside the spans, averaged over devices;
    None when no such span lies in the window."""
    spans = host_intervals(trace, names, lo, hi)
    if not spans:
        return None
    per = [intersect_ns(xplane.gaps(trace.ops[d], lo, hi), spans)
           for d in trace.devices]
    return sum(per) / len(per)


# --------------------------------------------------------------------------
# op -> scope path
# --------------------------------------------------------------------------

def hlo_paths(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        p = _OP_NAME.search(line)
        if m and p:
            out[m.group(1)] = p.group(1)
    return out


def op_paths(pd, modules: Sequence[str],
             hlo: Optional[Dict[str, str]] = None,
             device_plane: str = xplane.DEVICE_PLANE
             ) -> Dict[str, List[Tuple[float, float, str]]]:
    """Per device plane, ``(start, end, path)`` of the op events that ran
    inside the executables matching ``modules`` and whose ``op_name`` is
    known: from the event's name where it is the instruction's HLO text
    with its metadata, else from ``hlo`` (instruction -> ``op_name`` of
    those executables, ``hlo_paths``).  A v5e trace names each op by its
    instruction's text and carries no ``op_name`` stat."""
    rx = [re.compile(p) for p in modules]
    out: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith(device_plane):
            continue
        lines = {line.name: line for line in plane.lines}
        if xplane.MODULES_LINE not in lines or xplane.OPS_LINE not in lines:
            continue
        inside = xplane.union(
            [(m.start_ns, m.end_ns, m.name)
             for m in lines[xplane.MODULES_LINE].events
             if any(r.search(m.name) for r in rx)])
        evs, k = [], 0
        for e in sorted(lines[xplane.OPS_LINE].events,
                        key=lambda e: e.start_ns):
            while k < len(inside) and inside[k][1] < e.start_ns:
                k += 1
            if k == len(inside):
                break
            if e.start_ns < inside[k][0]:
                continue
            m = _OP_NAME.search(e.name)
            path = m.group(1) if m else (hlo or {}).get(
                xplane.module_name(e.name).lstrip("%"))
            if path is not None:
                evs.append((float(e.start_ns), float(e.end_ns), path))
        out[plane.name] = exclusive(evs)
    return out


def exclusive(ops: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Non-overlapping pieces of ``ops``: each instant some op covers
    goes to the latest-started op covering it (a loop's body ops take
    their time from the loop op; an op that starts before another ends
    takes the overlap), so per-scope sums never exceed the busy time."""
    ops = sorted(ops)
    times = sorted({t for s, e, _ in ops for t in (s, e)})
    active: List[Tuple[float, float, str]] = []
    out: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in zip(times, times[1:]):
        while i < len(ops) and ops[i][0] <= a:
            heapq.heappush(active, (-ops[i][0], ops[i][1], ops[i][2]))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if not active:
            continue
        path = active[0][2]
        if out and out[-1][1] == a and out[-1][2] == path:
            out[-1] = (out[-1][0], b, path)
        else:
            out.append((a, b, path))
    return out


def scope_ns(trace: xplane.Trace, paths: Dict[str, list],
             modules: Sequence[str], scope: str, lo: float,
             hi: float) -> Optional[float]:
    """Device nanoseconds of the ops whose path holds ``/<scope>/``,
    inside the executables matching ``modules``, averaged over devices;
    None when no such op ran there."""
    rx = [re.compile(p) for p in modules]
    tot, hit = 0.0, False
    for d in trace.devices:
        inside = xplane.union(xplane.clip(
            [ev for ev in trace.modules.get(d, [])
             if any(r.search(ev[2]) for r in rx)], lo, hi))
        ops = [(s, e, p) for s, e, p in paths.get(d, [])
               if f"/{scope}/" in p]
        ns = intersect_ns(xplane.union(xplane.clip(ops, lo, hi)), inside)
        hit = hit or ns > 0
        tot += ns
    return tot / len(trace.devices) if hit else None


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------

@metrics.kind("span_idle_ms_per_round")
def span_idle_ms_per_round(spec: Dict, ctx: Dict) -> Optional[float]:
    """Device idle ms per seed-round inside the named host spans."""
    ns = span_idle_ns(ctx["trace"], spec["spans"], ctx["lo"], ctx["hi"])
    if ns is None or not ctx["rounds"]:
        return None
    return 1e-6 * ns / ctx["rounds"]


@metrics.kind("scope_ms_per_round")
def scope_ms_per_round(spec: Dict, ctx: Dict) -> Optional[float]:
    """Device ms per seed-round of one named scope inside the named
    executables."""
    ns = scope_ns(ctx["trace"], ctx.get("op_paths", {}), spec["modules"],
                  spec["scope"], ctx["lo"], ctx["hi"])
    if ns is None or not ctx["rounds"]:
        return None
    return 1e-6 * ns / ctx["rounds"]


@metrics.kind("counter_share")
def counter_share(spec: Dict, ctx: Dict) -> Optional[float]:
    """100 x ``counter`` / the sum of the counters ``of``."""
    c = ctx["counters"]
    if spec["counter"] not in c or not all(k in c for k in spec["of"]):
        return None
    den = sum(c[k] for k in spec["of"])
    return 100.0 * c[spec["counter"]] / den if den else None


# --------------------------------------------------------------------------
# the breakdown
# --------------------------------------------------------------------------

def idle_by_span(trace: xplane.Trace, lo: float, hi: float,
                 rounds: int) -> Dict[str, float]:
    """Device idle ms per round inside each ``fl.*`` span name (a nested
    span counts in its parent too), in all, and outside every ``fl.*``
    span but ``fl.round``."""
    names = sorted({n for _, _, n, _ in trace.host
                    if n.startswith(SPAN_PREFIX)})
    out = {n: 1e-6 * span_idle_ns(trace, [n], lo, hi) / rounds
           for n in names}
    idle = sum(sum(e - s for s, e in xplane.gaps(trace.ops[d], lo, hi))
               for d in trace.devices) / len(trace.devices)
    covered = span_idle_ns(trace, [n for n in names if n != "fl.round"],
                           lo, hi) or 0.0
    out["idle"] = 1e-6 * idle / rounds
    out["outside fl.* child spans"] = 1e-6 * (idle - covered) / rounds
    return out


def top_ops(trace: xplane.Trace, paths: Dict[str, list], lo: float,
            hi: float, top: int = 12) -> List[List]:
    """The ops that took most device time, each with the executable it
    ran in and its scope path: ``[name, module, path, seconds]``."""
    tot: Dict[Tuple[str, str, str], float] = defaultdict(float)
    for d in trace.devices:
        mods = sorted(trace.modules.get(d, []))
        # an op owns the exclusive piece that starts when it starts
        path_at = {s: p for s, _, p in paths.get(d, [])}
        k = 0
        for s, e, n in sorted(xplane.clip(trace.ops[d], lo, hi)):
            while k < len(mods) and mods[k][1] < s:
                k += 1
            mod = (xplane.module_name(mods[k][2])
                   if k < len(mods) and mods[k][0] <= s else "?")
            tot[(xplane.module_name(n), mod, path_at.get(s, ""))] += e - s
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, m, p, t * 1e-9] for (n, m, p), t in best]


# --------------------------------------------------------------------------
# one traced window
# --------------------------------------------------------------------------

def prefix_hlo(sim) -> Dict[str, str]:
    """Instruction -> ``op_name`` of the prefix executables a window of
    ``sim`` runs: its stage config's election, then the dense re-run's
    (an instruction name in both keeps the first).  Each compile is a
    hit in the persistent cache the window filled."""
    import dataclasses

    import jax.numpy as jnp

    from repro.fl import pipeline
    out: Dict[str, str] = {}
    for elect in dict.fromkeys((sim.stage_cfg.elect, "gather")):
        cfg = dataclasses.replace(sim.stage_cfg, elect=elect)
        text = pipeline.selection_prefix.lower(
            sim.statics, sim.params, jnp.int32(0), sim.key, sim.net_key,
            cfg=cfg).compile().as_text()
        for k, v in hlo_paths(text).items():
            out.setdefault(k, v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    os.environ.setdefault("TPU_LOG_DIR", str(root / ".bench" / "tpu_logs"))
    import dataclasses

    import jax
    from jax.profiler import ProfileData

    from benchmarks.chip import cells, harness
    from repro.launch.cache import enable_jit_cache, resolve_cache_dir
    resolved = cells.resolve(args.workload, root)
    try:
        dev = harness.device_block(resolved["cell"]["chips"])
    except harness.NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    enable_jit_cache(resolve_cache_dir(str(root / ".jit-cache")))
    jax.config.update("jax_compilation_cache_max_size", -1)
    run = harness.measure(resolved, args.seed, args.seconds, True, root)
    clock, sim = run["clock"], run["sim"]
    rounds = len(clock.times)
    pd = ProfileData.from_file(xplane.find_xplane(str(run["tdir"])))
    tr = xplane.from_profile(pd)
    lo, hi = xplane.span(tr, "bench.window")
    red = xplane.reduce_window(tr, lo, hi)
    paths = op_paths(pd, PREFIX_MODULES, prefix_hlo(sim))
    ctx = {"window": red, "trace": tr, "lo": lo, "hi": hi,
           "rounds": rounds, "op_paths": paths,
           "counters": dict(dataclasses.asdict(sim.counters),
                            backend_compile=run["counter"].in_window)}
    specs = {m["name"]: m["spec"] for m in resolved["per_layer"]
             if m["spec"]["kind"] not in ("roofline", "mfu")}
    for name in SPAN_METRICS:
        specs[name] = cells.load_json(cells.HERE / "metrics" /
                                      f"{name}.json")
    out = {name: metrics.read(spec, ctx) for name, spec in specs.items()}
    scopes = {scope: scope_ms_per_round(
        {"modules": PREFIX_MODULES, "scope": scope}, ctx)
        for scope in ("positions", "probe", "elect", "deadline")}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": dev,
        "rounds": rounds, "window_s": red["window_s"],
        "busy_s": red["busy_s"], "metrics": out,
        "prefix_scope_ms": scopes, "counters": ctx["counters"],
        "idle_by_span_ms": idle_by_span(tr, lo, hi, rounds),
        "prefix_ops_with_path": sum(len(v) for v in paths.values()),
        "top_ops": top_ops(tr, paths, lo, hi),
        "idle_gaps": red["idle_gaps"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
