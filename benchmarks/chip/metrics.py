"""Per-layer metrics: each ``metrics/<name>.json`` names one reader kind
below and its arguments.  A reader that finds nothing to read returns
None, and the harness leaves that metric out of the line.

The context a reader gets (built by the harness from the traced run):

- ``window``: ``xplane.reduce_window`` of the traced window;
- ``trace``, ``lo``, ``hi``: the raw trace and the window's bounds (ns);
- ``rounds``: seed-rounds completed in the window;
- ``work``: required FLOPs and bytes of the window, from shapes
  (``work.py``): ``probe_flops``, ``probe_bytes``, ``train_flops``,
  ``eval_flops``;
- ``counters``: program counters read in the window
  (``backend_compile``);
- ``kind``, ``chips``: the device kind and the chips used.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

from benchmarks.chip import work, xplane

Reader = Callable[[Dict, Dict], Optional[float]]
KINDS: Dict[str, Reader] = {}


def kind(name: str):
    def reg(fn: Reader) -> Reader:
        KINDS[name] = fn
        return fn
    return reg


def _module_s(ctx: Dict, patterns) -> Optional[float]:
    """Device seconds of the matching executables, averaged over chips."""
    tot, hit = 0.0, False
    for d in ctx["trace"].devices:
        ns = xplane.matching_ns(ctx["trace"].modules.get(d, []), patterns,
                                ctx["lo"], ctx["hi"])
        if ns is not None:
            tot, hit = tot + ns, True
    return tot * 1e-9 / len(ctx["trace"].devices) if hit else None


@kind("idle_share")
def idle_share(spec: Dict, ctx: Dict) -> Optional[float]:
    """Percent of the window in which no operation ran on the device."""
    w = ctx["window"]
    return 100.0 * (1.0 - w["busy_s"] / w["window_s"])


@kind("counter")
def counter(spec: Dict, ctx: Dict) -> Optional[float]:
    return ctx["counters"].get(spec["counter"])


@kind("module_ms_per_round")
def module_ms_per_round(spec: Dict, ctx: Dict) -> Optional[float]:
    """Device milliseconds per seed-round of the named executables."""
    s = _module_s(ctx, spec["modules"])
    if s is None or not ctx["rounds"]:
        return None
    return 1e3 * s / ctx["rounds"]


@kind("roofline")
def roofline(spec: Dict, ctx: Dict) -> Optional[float]:
    """Percent of the executables' device time that the chip's roofline
    needs for their required work: max(FLOPs / peak, bytes / bandwidth)
    over the measured seconds."""
    s = _module_s(ctx, spec["modules"])
    w = ctx["work"]
    flops, nbytes = w[spec["flops"]], w[spec["bytes"]]
    if not s or not flops:
        return None
    return 100.0 * work.roofline_s(flops, nbytes, ctx["kind"])["s"] / s


@kind("mfu")
def mfu(spec: Dict, ctx: Dict) -> Optional[float]:
    """Percent of the chips' peak FLOP/s that the window's required
    model FLOPs use over the whole window."""
    flops = sum(ctx["work"][k] for k in spec["flops"])
    if not flops:
        return None
    peak = work.peak(ctx["kind"])["flops"] * ctx["chips"]
    return 100.0 * flops / ctx["window"]["window_s"] / peak


def read(spec: Dict, ctx: Dict) -> Optional[float]:
    if spec["kind"] not in KINDS:
        raise KeyError(f"unknown metric kind {spec['kind']!r} "
                       f"(known: {sorted(KINDS)})")
    return KINDS[spec["kind"]](spec, ctx)
