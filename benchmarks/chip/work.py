"""Chip peaks, the roofline, and the rows of the Eq. 7 probe.

Each family (``families/<name>.py``) counts the work its model
requires, from shapes: only the work the algorithm needs counts, so
padding rows, sentinel rows and any implementation's extra operations
never do, and a share computed from those numbers reads the same
whatever implements the work.
"""
from __future__ import annotations

from typing import Dict, Iterable

# published peaks of one chip, keyed by jax's ``device_kind``
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e: "
                              "197 TFLOP/s bf16, 819 GB/s HBM"},
}


def peak(kind: str) -> Dict:
    """The peaks of ``kind``; an unknown chip is an error, not a default."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def probe_rows(n_valid: Iterable[int], probe_samples: int) -> int:
    """Valid probe rows of one round: each vehicle's first
    ``min(n_valid, probe_samples)`` samples."""
    return int(sum(min(int(n), probe_samples) for n in n_valid))


def roofline_s(flops: float, nbytes: float, kind: str) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    p = peak(kind)
    t_c, t_m = flops / p["flops"], nbytes / p["hbm_bytes_per_s"]
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m
            else "memory"}
