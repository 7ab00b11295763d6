"""The comparison that decides ``correct``.

Each kept round is compared stage by stage with the plain reference
(``reference.py``).  Where a stage takes an earlier stage's decision as
its input, the reference is handed the program's decision (as a served
model's reference is handed the served tokens), so a gap points at the
stage that made it.

Stages without a matmul compute the same float32 formulas on both sides
and are compared exactly, with the limit 0:

- ``replay``: the round re-run through the same compiled prefix after
  the window, against the window's own report (mask, selected,
  aggregated and straggler counts, mean evaluation);
- ``pos_diff``, ``feat_diff``, ``eval_diff``: vehicles whose position,
  SQ / TA / CC column, or evaluation (the reference's fuzzy inference on
  the program's features) is off by more than ``ULPS`` float32 units in
  the last place of the quantity's scale;
- ``elect_diff``, ``survivor_diff``: vehicles on which the election (on
  the program's positions and evaluations) and the deadline (on its
  mask) disagree;
- ``acc_outside``: test items by which the accuracy count falls
  outside the reference's interval: its sure count, plus the items
  whose top two logits lie within ``MARGIN`` (a rounding can flip those
  either way).

The model's stages come from the configuration's family
(``World.family``); the rest is the same for every model.  The matmul
stages carry the precision; their limits lie between the
program's readings and those of the control or of a planted fault
(``control.py``, ``PERF.md``):

- ``lf_gap``: the Eq. 7 probe loss, widest gap over vehicles (nats);
- ``param_gap``: the global params after FedAvg, taken by the worst
  trained leaf (the family's ``trained_leaves``) as the gap between the
  norms of the program's and the reference's change of that leaf, over
  the larger of the reference's norm for that leaf and for the median
  leaf.  Norms, not the norm of the difference: local SGD over tens of
  steps can amplify last-bit differences into a different trajectory of
  the same size.  A round that returns its params unchanged reads 1;
- ``fit_gap``: the mean loss of the global params after FedAvg on the
  round's survivors' own training rows, program against reference, over
  the same loss of the params that entered the round.  Two trajectories
  that round-off set apart fit those rows alike; a trainer that skips
  rows leaves them unfitted.  A round without survivors reads 0.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.chip import reference as ref

NUMBERS = ("replay", "pos_diff", "feat_diff", "eval_diff", "elect_diff",
           "survivor_diff", "acc_outside", "lf_gap", "param_gap", "fit_gap")
ULPS = 16
MARGIN = 1e-4          # logits; float32 rounding of the forward is ~1e-6


def off(a, b, scale) -> int:
    """Entries of ``a`` farther from ``b`` than ``ULPS`` float32 units in
    the last place of ``scale`` (an array, or one number)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    tol = ULPS * np.spacing(np.abs(np.asarray(scale, np.float32)))
    return int(np.sum(np.abs(a - b) > tol))


def param_gap(leaves, p_in, p_out, p_ref) -> float:
    """Worst leaf's ``| |p_out - p_in| - |p_ref - p_in| |`` over
    ``max(|p_ref - p_in|, median leaf's |p_ref - p_in|)``, over the
    ``leaves(params)`` that train.  When the reference moved nothing (no
    survivor), anything but the unchanged params reads 1."""
    pin, pout, pref = (leaves(t) for t in (p_in, p_out, p_ref))
    change = [np.linalg.norm(r - i) for r, i in zip(pref, pin)]
    floor = float(np.median(change))
    if floor == 0.0:
        return 0.0 if all(np.array_equal(o, i) for o, i in
                          zip(pout, pin)) else 1.0
    return max(abs(float(np.linalg.norm(o - i)) - c) / max(c, floor)
               for o, i, c in zip(pout, pin, change))


def fit_gap(world: ref.World, survivors, p_in, p_out, p_ref) -> float:
    """``|L(p_out) - L(p_ref)| / L(p_in)``, ``L`` the mean loss over the
    survivors' valid rows; 0 without survivors."""
    ids = np.nonzero(np.asarray(survivors))[0]
    if len(ids) == 0:
        return 0.0
    fit = [world.family.fit_loss(world, ids, p) for p in (p_in, p_out, p_ref)]
    return abs(fit[1] - fit[2]) / fit[0]


def compare_round(world: ref.World, conf: Dict, rnd: int, p_in,
                  out: Dict) -> Dict[str, float]:
    """The numbers of one round.  ``out`` is what the program (or the
    control in its place) produced: ``pos``, ``feats``, ``evals``,
    ``mask``, ``survivors``, ``params`` and the accuracy ``count``."""
    sim, fam = conf["sim"], world.family
    pos = ref.positions(world, conf, rnd)
    k_pred, k_up = ref.round_keys(world, conf, rnd)
    feats = np.asarray(out["feats"], np.float64)
    aux = np.stack([world.n_valid, np.asarray(ref.throughput(
        conf, out["pos"], k_pred)), 1.0 / np.asarray(world.slowdown,
                                                     np.float32)], axis=1)
    reach = (conf["mobility"]["road_length_m"] + conf["mobility"]["v_max_mps"]
             * rnd * sim["deadline_s"])
    lo, hi = fam.count_correct(world, out["params"], margin=MARGIN)
    p_ref = ref.fedavg_round(world, conf, p_in, out["survivors"], rnd)
    return {
        "pos_diff": off(out["pos"], pos, reach),
        "feat_diff": off(feats[:, :3], aux, aux),
        "eval_diff": off(out["evals"], ref.fuzzy(conf, np.asarray(
            out["feats"])), conf["fuzzy"]["scale"]),
        "elect_diff": int(np.sum(np.asarray(out["mask"]) != ref.elect(
            conf, out["pos"], out["evals"]))),
        "survivor_diff": int(np.sum(
            np.asarray(out["survivors"]).astype(bool)
            != ref.deadline(world, conf, out["pos"], out["mask"], k_up))),
        "acc_outside": max(0, lo - int(out["count"]),
                           int(out["count"]) - hi),
        "lf_gap": float(np.max(np.abs(
            feats[:, 3] - np.asarray(fam.probe_loss(world, conf, p_in))))),
        "param_gap": param_gap(fam.trained_leaves, p_in, out["params"],
                               p_ref),
        "fit_gap": fit_gap(world, out["survivors"], p_in, out["params"],
                           p_ref),
    }


def worst(per_round: List[Dict[str, float]]) -> Dict[str, float]:
    """Each number's largest reading over the checked rounds."""
    return {k: max(float(r[k]) for r in per_round)
            for k in per_round[0]}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``correct`` and each number beside its limit.  A number without a
    limit, or one that could not be read, fails."""
    items, ok = {}, True
    for k in NUMBERS:
        v, lim = numbers.get(k), limits.get(k)
        good = (v is not None and lim is not None
                and np.isfinite(v) and v <= lim)
        ok &= bool(good)
        items[k] = {"value": v, "limit": lim}
    return {"correct": ok, "compared": items}
