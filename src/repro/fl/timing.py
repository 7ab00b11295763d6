"""Training-time model and straggler handling (paper §5.2, Eq. 6).

Eq. 6 as printed —  T = E*C_i*|D_i| / (B_size*B_exe)  — is dimensionally
inconsistent with the paper's own definition of B_exe ("the time to train
the model ... for B_size samples", 0.06 s): dividing by seconds yields
1/s.  We implement the dimensionally consistent reading

    T_i = E * C_i * |D_i| * B_exe / B_size                    [seconds]

where C_i >= 1 is the *slowdown* ratio of vehicle i relative to the
reference machine that measured B_exe (C_i = 1/capability).  With the
paper's Table 3 values this gives big vehicles (4500 samples, E=30,
B=20, B_exe=0.06 s) T = 405 s at C_i=1 — far beyond the 20 s deadline,
which is why the deadline/straggler mechanism and per-round epoch budget
matter; the simulator makes E configurable.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class TimingConfig:
    epochs: int = 30
    batch_size: int = 20
    b_exe_s: float = 0.06          # measured on the paper's i5 reference
    deadline_s: float = 20.0


def training_time_s(cfg: TimingConfig, slowdown: np.ndarray,
                    n_samples: np.ndarray) -> np.ndarray:
    """T_i = E * C_i * |D_i| * B_exe / B_size  (vectorized)."""
    return (cfg.epochs * slowdown * n_samples * cfg.b_exe_s
            / cfg.batch_size)


def completes_before_deadline(cfg: TimingConfig, train_s: np.ndarray,
                              upload_s: np.ndarray) -> np.ndarray:
    """Straggler mask: local models arriving after the deadline are
    discarded (paper §6.1)."""
    return (train_s + upload_s) <= cfg.deadline_s


def staleness_weight(lam: float, delay_rounds) -> np.ndarray:
    """Staleness-weighted aggregation weight ``1 / (1 + lambda * d)``
    for an update aggregated ``d`` rounds after the round whose global
    model it was trained from (event-driven server, ISSUE 6).

    ``d = 0`` (on time) always weighs 1; ``lam = 0`` disables the decay
    (every late update counts fully); works on scalars and arrays.  The
    hard-deadline Eq. 6 policy is the ``lam -> inf`` limit restricted to
    {1 at deadline, 0 after} — the event server's "drop" mode pins that
    limit exactly rather than approximating it."""
    if lam < 0.0:
        raise ValueError(f"staleness lambda must be >= 0: {lam}")
    if np.any(np.asarray(delay_rounds) < 0):
        raise ValueError(f"delay_rounds must be >= 0: {delay_rounds}")
    return 1.0 / (1.0 + lam * delay_rounds)

