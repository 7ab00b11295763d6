"""Required work of the paper CNN, counted from shapes, and chip peaks.

Only the work the algorithm needs counts: the probe forward over each
vehicle's valid probe samples, three forwards (forward + backward) for
every valid sample a trained vehicle trains on, and the test-set
forward of the accuracy read.  Padding rows, sentinel rows and any
implementation's extra operations never count, so a share computed from
these numbers reads the same whatever implements the work.
"""
from __future__ import annotations

from typing import Dict, Iterable

# the paper CNN's widths (arXiv:2401.03159 Section 6.1, as the repo's
# configs/mnist_cnn.py sizes it): conv 5x5x1->32, pool, conv 5x5x32->64,
# pool, fc 3136->512, fc 512->10, SAME convolutions on 28x28x1 images
IMAGE, KERNEL, C1, C2, FC, CLASSES = 28, 5, 32, 64, 512, 10
FLAT = (IMAGE // 4) ** 2 * C2

# forward multiply-accumulates per image row (conv1 + conv2 + fc1 + fc2)
MACS_PER_ROW = (28 * 28 * 25 * 1 * 32 + 14 * 14 * 25 * 32 * 64
                + 3136 * 512 + 512 * 10)
FWD_FLOPS_PER_ROW = 2 * MACS_PER_ROW
# forward + backward of one training row: the backward pass costs two
# forwards (input and weight gradients)
TRAIN_FLOPS_PER_ROW = 3 * FWD_FLOPS_PER_ROW

PARAM_COUNT = ((KERNEL * KERNEL * 1 * C1 + C1)
               + (KERNEL * KERNEL * C1 * C2 + C2)
               + (FLAT * FC + FC) + (FC * CLASSES + CLASSES))
F32 = 4
PARAM_BYTES = PARAM_COUNT * F32
# one image (28 x 28 x 1 float32) and its int32 label
ROW_BYTES = IMAGE * IMAGE * F32 + 4

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


def probe_work(n_valid: Iterable[int], probe_samples: int) -> Dict:
    """FLOPs and bytes the Eq. 7 probe needs: the forward of every valid
    probe row, reading each row once and the params once."""
    rows = probe_rows(n_valid, probe_samples)
    return {"flops": rows * FWD_FLOPS_PER_ROW,
            "bytes": rows * ROW_BYTES + PARAM_BYTES}


def train_flops(n_valid_trained: Iterable[int], epochs: int) -> int:
    """Local SGD of the trained vehicles: every valid sample once per
    epoch, forward and backward."""
    return int(sum(int(n) for n in n_valid_trained)) * epochs \
        * TRAIN_FLOPS_PER_ROW


def eval_flops(n_test: int) -> int:
    """The accuracy read: one forward per test image."""
    return int(n_test) * FWD_FLOPS_PER_ROW


def roofline_s(flops: float, nbytes: float, kind: str) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    p = peak(kind)
    t_c, t_m = flops / p["flops"], nbytes / p["hbm_bytes_per_s"]
    return {"s": max(t_c, t_m), "bound": "compute" if t_c >= t_m
            else "memory"}
