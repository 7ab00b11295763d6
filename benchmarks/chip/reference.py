"""A plain reference of one FL round, in float32 at ``highest`` precision.

It imports nothing of the program.  It reads the cell's world (each
vehicle's slowdown and mobility constants, and the family's data: each
vehicle's samples and the test set) and the configuration file, and
recomputes the round as the paper and the configuration define it:

- positions on the wrap-around freeway (closed-form speed jitter);
- the throughput predictor: Reno AIMD over 64 RTTs against a loss
  probability that rises toward the cell edge, averaged over the last
  16 windows;
- the Eq. 7 probe: the model's mean loss over each vehicle's first
  ``probe_samples`` samples (the family's ``probe_loss``);
- Eq. 8 max-scaling and Mamdani inference over the 81-rule base
  (min conjunction, max aggregation, centre of gravity on [0, 100]);
- the DCS election: a vehicle above ``e_tau`` elects itself when fewer
  than ``top_m`` in-range vehicles above ``e_tau`` rank above it;
- the Eq. 6 deadline: ``E * C_i * |D_i| * B_exe / B + upload``;
- Eq. 1 local SGD on each survivor (the family's ``local_train``) and
  the Eq. 2 FedAvg;
- the accuracy count on the test set (the family's ``count_correct``).

The model is the configuration's family (``families/<name>.py``, which
``World.family`` holds); everything else here is the same for every
model.  The random draws (channel shadowing, Reno losses, epoch
shuffles) use the seed schedule the configuration documents, so a
round's randomness is the same realization the program draws.

Run under ``jax.default_matmul_precision("highest")`` it is the
reference.  ``dtype=bfloat16`` at the default precision is the control:
the model (forward, training, FedAvg) and the fuzzy evaluator computed
in the next precision below the configuration's float32.
"""
from __future__ import annotations

import functools
import itertools
import json
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


@dataclass
class World:
    """The data a round reads, on the host.  Per vehicle ``i``:
    ``n_valid[i]`` valid samples, ``slowdown``, the mobility constants.
    ``data`` is the family's own (each vehicle's samples and the test
    set, as ``family.world_data`` copies them); ``family`` is the module
    of the model the configuration trains."""
    n_valid: np.ndarray
    slowdown: np.ndarray
    x0: np.ndarray
    speeds: np.ndarray
    jitter_phase: np.ndarray
    seed: int                    # the deployment: init, training keys
    net_seed: int                # the channel realizations
    data: Any
    family: Any

    @property
    def n(self) -> int:
        return len(self.n_valid)


def cast(tree, dtype):
    """``tree`` with every leaf in ``dtype``."""
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), tree)


# --------------------------------------------------------------------------
# the round's stages
# --------------------------------------------------------------------------

def key(conf: Dict) -> str:
    """A configuration as a hashable jit-static."""
    return json.dumps(conf, sort_keys=True)


def staged(fn):
    """Jit a stage whose constants come from the configuration, passed as
    the static ``ck`` (``key(conf)``), so each stage compiles once."""
    jitted = jax.jit(lambda *a, ck, **kw: fn(json.loads(ck), *a, **kw),
                     static_argnames=("ck", "dtype"))

    @functools.wraps(fn)
    def call(conf, *args, **kw):
        return jitted(*args, ck=key(conf), **kw)
    return call


@staged
def _positions(conf, x0, speeds, phase, rnd):
    mob, period = conf["mobility"], conf["mobility_model"]["jitter_period_s"]
    t = rnd.astype(F32) * jnp.float32(conf["sim"]["deadline_s"])
    jitter = mob["speed_jitter"] * period * (
        jnp.cos(phase) - jnp.cos(t / period + phase))
    return jnp.mod(x0 + speeds * t + jitter, mob["road_length_m"])


def positions(world: World, conf: Dict, rnd: int) -> jax.Array:
    """Freeway positions at the round's start, ``rnd * deadline`` s."""
    return _positions(conf, jnp.asarray(world.x0, F32),
                      jnp.asarray(world.speeds, F32),
                      jnp.asarray(world.jitter_phase, F32), jnp.int32(rnd))


def round_keys(world: World, conf: Dict, rnd: int):
    """(predictor key, upload key) of round ``rnd``: the channel base
    ``PRNGKey(network seed + 53)`` with the simulation seed folded in,
    then the round folded in and split in two."""
    base = jax.random.fold_in(jax.random.PRNGKey(world.net_seed + 53),
                              world.seed)
    k_pred, k_up = jax.random.split(jax.random.fold_in(base, rnd))
    return k_pred, k_up


def rate_bps(conf: Dict, pos: jax.Array, shadow: jax.Array) -> jax.Array:
    """Achievable rate: log-scale between the worst and best MCS by the
    distance to the nearest base station, with log-normal shadowing."""
    net = conf["network"]
    n_bs, road = net["n_bs"], net["road_length_m"]
    bs = (jnp.arange(n_bs) + 0.5) * (road / n_bs)
    d = jnp.min(jnp.abs(pos[:, None] - bs[None, :]), axis=1)
    frac = jnp.clip(1.0 - d / (road / n_bs / 2.0), 0.0, 1.0)
    lo, hi = np.log10(net["worst_rate_bps"]), np.log10(net["best_rate_bps"])
    return 10.0 ** (lo + frac * (hi - lo)
                    + shadow * (net["shadowing_sigma_db"] / 10.0))


@staged
def throughput(conf: Dict, pos: jax.Array, k_pred) -> jax.Array:
    """The CWND-average predictor (paper Section 5.1), bps-equivalent."""
    net, n = conf["network"], pos.shape[0]
    rate = rate_bps(conf, pos, jax.random.normal(jax.random.PRNGKey(0),
                                                 (n,)))
    lo, hi = np.log10(net["worst_rate_bps"]), np.log10(net["best_rate_bps"])
    p_loss = jnp.clip(0.08 * (1.0 - (jnp.log10(rate) - lo) / (hi - lo))
                      + 0.002, 0.002, 0.2)
    bdp = rate * net["rtt_s"] / (8.0 * net["packet_bytes"])
    u = jnp.stack([jax.random.uniform(k, (n,))
                   for k in jax.random.split(k_pred, 64)])
    cwnd, hist = jnp.ones(n), []
    for t in range(64):
        cwnd = jnp.where(u[t] < p_loss, jnp.maximum(cwnd / 2.0, 1.0),
                         cwnd + 1.0)
        cwnd = jnp.minimum(cwnd, jnp.maximum(bdp, 1.0))
        hist.append(cwnd)
    window = jnp.stack(hist[-net["cwnd_history"]:])
    return window.mean(axis=0) * 8.0 * net["packet_bytes"] / net["rtt_s"]


def rule_table():
    """The 81 rules: antecedent levels (SQ, TA, CC, LF), 0 the worst, and
    the consequent L0..L8 — the sum of the four levels, except when TA
    and CC are both at their worst (the upload bottleneck), where only
    the data counts: SQ * LF."""
    rows, levels = [], []
    for lf, ta, cc, sq in itertools.product(range(2, -1, -1), repeat=4):
        rows.append((sq, ta, cc, lf))
        levels.append(sq * lf if ta == 0 and cc == 0 else sq + ta + cc + lf)
    return np.asarray(rows), np.asarray(levels)


@staged
def fuzzy(conf: Dict, feats: jax.Array, dtype=F32) -> jax.Array:
    """Eq. 8 max-scaling, Gaussian memberships, Mamdani inference and
    centre of gravity: (N, 4) raw features -> (N,) on [0, scale]."""
    fz = conf["fuzzy"]
    x = feats.astype(dtype)
    x = jnp.clip(x / jnp.maximum(x.max(axis=0), 1e-9), 0.0, 1.0)
    means = jnp.asarray(fz["means"], dtype)                  # (3,)
    mu = jnp.exp(-0.5 * jnp.square((x[:, :, None] - means)
                                   / jnp.asarray(fz["sigma"], dtype)))
    rows, levels = rule_table()
    firing = jnp.stack([jnp.min(jnp.stack([mu[:, v, r[v]] for v in range(4)]),
                                axis=0) for r in rows], axis=1)  # (N, 81)
    n_out = fz["levels"]
    beta = jnp.stack([jnp.max(firing[:, levels == k], axis=1)
                      if (levels == k).any() else jnp.zeros_like(x[:, 0])
                      for k in range(n_out)], axis=1)
    centers = jnp.linspace(0.0, fz["scale"], n_out).astype(dtype)
    out = (beta * centers).sum(-1) / jnp.maximum(beta.sum(-1), 1e-9)
    return out.astype(F32)


def elect(conf: Dict, pos: jax.Array, evals: jax.Array) -> np.ndarray:
    """DCS: selected iff ``eval >= e_tau`` and fewer than ``top_m``
    vehicles within ``comm_range_m`` that also clear ``e_tau`` rank above
    it (higher evaluation, or equal with a lower index)."""
    sim = conf["sim"]
    pos, ev = np.asarray(pos, np.float32), np.asarray(evals, np.float32)
    n = len(ev)
    near = np.abs(pos[:, None] - pos[None, :]) <= np.float32(
        sim["comm_range_m"])
    idx = np.arange(n)
    above = (ev[None, :] > ev[:, None]) | (
        (ev[None, :] == ev[:, None]) & (idx[None, :] < idx[:, None]))
    rivals = (near & (ev[None, :] >= sim["e_tau"]) & above).sum(axis=1)
    return ((ev >= sim["e_tau"]) & (rivals < sim["top_m"])).astype(np.int32)


@staged
def _in_time(conf, slowdown, n_valid, pos, k_up):
    sim = conf["sim"]
    train_t = (jnp.float32(sim["local_epochs"]) * slowdown * n_valid
               * jnp.float32(conf["timing"]["b_exe_s"])
               / jnp.float32(sim["batch_size"]))
    shadow = jax.random.normal(k_up, pos.shape)
    up_t = sim["model_bytes"] * 8.0 / rate_bps(conf, pos, shadow) + 0.2
    return train_t + up_t <= sim["deadline_s"]


def deadline(world: World, conf: Dict, pos: jax.Array, mask, k_up
             ) -> np.ndarray:
    """Eq. 6 survivors: selected vehicles whose local training plus the
    model upload end within the deadline."""
    ok = _in_time(conf, jnp.asarray(world.slowdown, F32),
                  jnp.asarray(world.n_valid, F32), jnp.asarray(pos, F32),
                  k_up)
    return (np.asarray(mask) > 0) & np.asarray(ok)


def train_key(world: World, rnd: int, i: int):
    """Vehicle ``i``'s key in round ``rnd``: ``PRNGKey(seed + 2)`` with
    the round, then the vehicle, folded in."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(world.seed + 2), rnd), i)


def fedavg_round(world: World, conf: Dict, p, survivors, rnd: int,
                 dtype=F32):
    """Eq. 1 on every survivor from the global ``p``, then Eq. 2: the
    average weighted by ``|D_i|``.  No survivor leaves ``p`` as it is."""
    ids = np.nonzero(np.asarray(survivors))[0]
    if len(ids) == 0:
        return p
    acc, tot = None, 0.0
    for i in ids:
        q = world.family.local_train(world, conf, p, i, rnd, dtype)
        w = jnp.asarray(float(world.n_valid[i]), dtype)
        acc = jax.tree.map(lambda a: w * a, q) if acc is None else \
            jax.tree.map(lambda a, b: a + w * b, acc, q)
        tot += float(world.n_valid[i])
    return jax.tree.map(lambda a: (a / jnp.asarray(tot, dtype)).astype(F32),
                        acc)


# --------------------------------------------------------------------------
# whole rounds
# --------------------------------------------------------------------------

def run_round(world: World, conf: Dict, p, rnd: int, dtype=F32) -> Dict:
    """The whole round from the global ``p``, every stage the
    reference's own: what the control puts in the program's place."""
    pos = positions(world, conf, rnd)
    k_pred, k_up = round_keys(world, conf, rnd)
    feats = jnp.stack([jnp.asarray(world.n_valid, F32),
                       throughput(conf, pos, k_pred),
                       1.0 / jnp.asarray(world.slowdown, F32),
                       world.family.probe_loss(world, conf, p, dtype)],
                      axis=1)
    evals = fuzzy(conf, feats, dtype=dtype)
    mask = elect(conf, pos, evals)
    surv = deadline(world, conf, pos, mask, k_up)
    out = fedavg_round(world, conf, p, surv, rnd, dtype)
    return {"pos": pos, "feats": feats, "evals": evals, "mask": mask,
            "survivors": surv, "params": out,
            "count": world.family.count_correct(world, out, dtype)[0]}
