"""Reduce a profiler trace (``.xplane.pb``) to device times.

The JAX profiler writes one XSpace per traced window.  Each accelerator
is a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line holds one
event per device operation and its ``XLA Modules`` line one event per
executable run.  Host threads are planes ``/host:...`` whose lines hold
the Python-side spans, the benchmark's own ``bench.*`` annotations among
them.  Every event has a start and an end in nanoseconds on one clock.

Everything here works on plain ``(start_ns, end_ns, name)`` tuples, so it
is tested on synthetic traces without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[float, float, str]

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPAN = "bench."


@dataclass
class Trace:
    """The events of one trace: per device plane, its op and module
    events; and every host event with the line it came from."""
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Tuple[float, float, str, str]] = field(default_factory=list)
    lines: Dict[str, List[str]] = field(default_factory=dict)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def from_profile(pd, device_plane: str = DEVICE_PLANE) -> Trace:
    """Collect a ``jax.profiler.ProfileData``'s events into a ``Trace``."""
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        tr.lines[name] = [line.name for line in plane.lines]
        if name.startswith(device_plane):
            for line in plane.lines:
                evs = [(float(e.start_ns), float(e.end_ns), e.name)
                       for e in line.events]
                if line.name == OPS_LINE:
                    tr.ops[name] = evs
                elif line.name == MODULES_LINE:
                    tr.modules[name] = evs
        elif name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((float(e.start_ns), float(e.end_ns), e.name,
                                line.name) for e in line.events)
    return tr


def read(path: str, device_plane: str = DEVICE_PLANE) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), device_plane)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------

def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to ``[lo, hi]``; those wholly outside are dropped."""
    out = []
    for s, e, n in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, n))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covered by any event."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some event runs."""
    return sum(e - s for s, e in union(clip(events, lo, hi)))


def gaps(events: Sequence[Event], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``: covered by no event."""
    out, t = [], lo
    for s, e in union(clip(events, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """An executable's or operation's short name: without the run id some
    traces append, and without the HLO text after an op's name."""
    return _SUFFIX.sub("", name.split(" = ")[0]).strip()


def time_by_name(events: Sequence[Event], lo: float,
                 hi: float) -> Dict[str, float]:
    """Total nanoseconds inside ``[lo, hi]`` per event name."""
    tot: Dict[str, float] = defaultdict(float)
    for s, e, n in clip(events, lo, hi):
        tot[module_name(n)] += e - s
    return dict(tot)


def matching_ns(events: Sequence[Event], patterns: Sequence[str],
                lo: float, hi: float) -> Optional[float]:
    """Nanoseconds of the events whose name matches any of ``patterns``
    (regular expressions), or None when no event matches."""
    rx = [re.compile(p) for p in patterns]
    hit = [ev for ev in events if any(r.search(ev[2]) for r in rx)]
    if not hit:
        return None
    return sum(e - s for s, e, _ in clip(hit, lo, hi))


# --------------------------------------------------------------------------
# windows and labels
# --------------------------------------------------------------------------

def span(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    """The first host span called ``name`` (start, end), if any."""
    for s, e, n, _ in sorted(trace.host):
        if n == name:
            return s, e
    return None


def label_at(trace: Trace, t: float) -> str:
    """What the host was doing at ``t``: the innermost ``bench.*`` span
    covering it, joined with the innermost other host span covering it
    on the same line (a dispatch, a transfer, a Python frame)."""
    bench = [(e - s, n, line) for s, e, n, line in trace.host
             if s <= t <= e and n.startswith(BENCH_SPAN)]
    if not bench:
        return "outside bench spans"
    _, name, line = min(bench)
    inner = [(e - s, n) for s, e, n, ln in trace.host
             if s <= t <= e and ln == line and not n.startswith(BENCH_SPAN)]
    return f"{name} / {min(inner)[1]}" if inner else name


def reduce_window(trace: Trace, lo: float, hi: float,
                  top: int = 10) -> Dict:
    """Per-window reduction over every device plane: busy seconds
    (averaged over devices), device seconds per executable (summed over
    devices), the operations that took most time, and the longest idle
    gaps labelled with what the host was doing meanwhile."""
    devs = trace.devices
    if not devs:
        raise ValueError(f"the trace holds no device plane with XLA ops; "
                         f"planes and lines: {trace.lines}")
    busy = [busy_ns(trace.ops[d], lo, hi) for d in devs]
    modules: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[float, float]] = []
    for d in devs:
        for n, t in time_by_name(trace.modules.get(d, []), lo, hi).items():
            modules[n] += t
        for n, t in time_by_name(trace.ops[d], lo, hi).items():
            ops[n] += t
        idle.extend(gaps(trace.ops[d], lo, hi))
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "modules_s": {n: t * 1e-9 for n, t in modules.items()},
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_at(trace, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }
