"""The paper CNN as a benchmark family.

A family is the model a configuration trains (its ``"family"`` key,
loaded by ``cells.family``).  It owns everything of the round that
depends on the model: the program's ``FLSimConfig``, each vehicle's
local data and the test set, the Eq. 7 probe, Eq. 1 local SGD, the
accuracy count, the loss on training rows, the leaves ``param_gap``
compares and the required work.  Every family module exposes:

- ``sim_config(conf)``: the program's ``FLSimConfig``;
- ``world_data(sim)``: the host copy of each vehicle's local data and
  of the test set (``World.data``);
- ``n_test(sim)``: the size of the test set the accuracy counts over;
- ``probe_loss(world, conf, p, dtype)``: Eq. 7, per vehicle;
- ``local_train(world, conf, p, i, rnd, dtype)``: Eq. 1 on vehicle
  ``i`` in round ``rnd`` from the global ``p``;
- ``count_correct(world, p, dtype, margin)``: ``(lo, hi)`` on the test
  set;
- ``fit_loss(world, ids, p)``: mean loss of ``p`` over the valid
  training rows of vehicles ``ids``;
- ``trained_leaves(p)``: the leaves that train, as float64 arrays;
- ``work(conf, world, rounds, trained_ids)``: the window's required
  ``probe_flops``, ``probe_bytes``, ``train_flops`` and ``eval_flops``.

This one is arXiv:2401.03159 Section 6.1's CNN: SAME 5x5 convs
1->32 and 32->64 with ReLU and 2x2 max-pooling, then fc 3136->512 and
512->10, on 28x28x1 images.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference as ref
from benchmarks.chip.work import probe_rows

F32 = ref.F32
BLOCK = 1024            # rows per forward block (keeps activations small)

# the widths (as the repo's configs/mnist_cnn.py sizes it)
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
F32_BYTES = 4
PARAM_BYTES = PARAM_COUNT * F32_BYTES
# one image (28 x 28 x 1 float32) and its int32 label
ROW_BYTES = IMAGE * IMAGE * F32_BYTES + 4


@dataclass
class Data:
    """Per vehicle ``i``: its samples ``images[i]`` (cap_i, 28, 28, 1)
    and ``labels[i]``, of which ``World.n_valid[i]`` leading rows are
    valid; the test set."""
    images: List[np.ndarray]
    labels: List[np.ndarray]
    test_images: np.ndarray
    test_labels: np.ndarray


# --------------------------------------------------------------------------
# the program's side
# --------------------------------------------------------------------------

def sim_config(conf: Dict):
    """The ``FLSimConfig`` of a configuration file.

    The deployment (data, partition, placement, speeds, slowdowns, the
    initial model and the channel realizations of every round) is the
    configuration's own ``deployment_seed``, so every run does the same
    work.  Any input the rounds read decides which vehicles are elected
    and meet Eq. 6, so it would change the work; a run's ``--seed``
    draws only the rounds the check compares."""
    from repro.fl.mobility import MobilityConfig
    from repro.fl.network import NetworkConfig
    from repro.fl.partition import PartitionConfig
    from repro.fl.rounds import FLSimConfig
    d = conf["deployment_seed"]
    sim = dict(conf["sim"])
    sim["slowdown_range"] = tuple(sim["slowdown_range"])
    return FLSimConfig(
        **sim, seed=d,
        partition=PartitionConfig(**conf["partition"], seed=d),
        mobility=MobilityConfig(**conf["mobility"], seed=d),
        network=NetworkConfig(**conf["network"], seed=d))


def world_data(sim) -> Data:
    """Each vehicle's samples and the test set as host arrays."""
    images, labels = [None] * sim.n, [None] * sim.n
    for g in sim.groups:
        gi, gl = np.asarray(g.images), np.asarray(g.labels)
        for row, i in enumerate(g.client_ids):
            images[i], labels[i] = gi[row], gl[row]
    return Data(images=images, labels=labels,
                test_images=np.asarray(jax.device_get(sim.test_images)),
                test_labels=np.asarray(jax.device_get(sim.test_labels)))


def n_test(sim) -> int:
    return int(sim.test_labels.shape[0])


# --------------------------------------------------------------------------
# the reference's side
# --------------------------------------------------------------------------

def forward(p: Dict, x: jax.Array) -> jax.Array:
    """(B, 28, 28, 1) -> logits (B, 10): SAME 5x5 convs with ReLU and 2x2
    max-pooling, then two dense layers."""
    for name in ("conv1", "conv2"):
        x = jax.lax.conv_general_dilated(
            x, p[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p[name]["b"]
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ p["fc1"]["w"] + p["fc1"]["b"])
    return x @ p["fc2"]["w"] + p["fc2"]["b"]


def nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-sample cross-entropy."""
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - gold


@functools.partial(jax.jit, static_argnames=("dtype",))
def _block_losses(p, x, y, dtype):
    return nll(forward(ref.cast(p, dtype), x.astype(dtype)), y).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "margin"))
def _block_correct(p, x, y, dtype, margin):
    logits = forward(ref.cast(p, dtype), x.astype(dtype))
    top2 = jax.lax.top_k(logits, 2)[0]
    near = (top2[:, 0] - top2[:, 1] < margin) & (y >= 0)
    right = (jnp.argmax(logits, -1) == y) & ~near
    return right.sum(), near.sum()


def _blocks(n: int):
    for s in range(0, n, BLOCK):
        yield s, min(s + BLOCK, n)


def _padded(a: np.ndarray, s: int, e: int) -> np.ndarray:
    blk = a[s:e]
    if e - s < BLOCK:
        pad = np.zeros((BLOCK - (e - s),) + a.shape[1:], a.dtype)
        blk = np.concatenate([blk, pad])
    return blk


def sample_losses(p, x: np.ndarray, y: np.ndarray, dtype=F32) -> jax.Array:
    """Per-sample losses of ``p`` over host rows, block by block."""
    out = [_block_losses(p, _padded(x, s, e), _padded(y, s, e),
                         dtype=dtype)[:e - s] for s, e in _blocks(len(y))]
    return jnp.concatenate(out)


def count_correct(world: ref.World, p, dtype=F32, margin: float = 0.0):
    """``(lo, hi)``: test images ``p`` classifies right for certain, and
    that count plus the images whose top two logits lie within
    ``margin`` of each other (a rounding could flip those either way)."""
    x, y = world.data.test_images, world.data.test_labels
    right = near = 0
    for s, e in _blocks(len(y)):
        xb, yb = _padded(x, s, e), _padded(y, s, e).copy()
        yb[e - s:] = -1                      # padding never counts
        r, n = _block_correct(p, xb, yb, dtype=dtype, margin=margin)
        right, near = right + int(r), near + int(n)
    return right, right + near


def probe_loss(world: ref.World, conf: Dict, p, dtype=F32) -> jax.Array:
    """Eq. 7 per vehicle: mean loss over its first ``probe_samples``."""
    take = np.minimum(world.n_valid, conf["sim"]["probe_samples"])
    x = np.concatenate([im[:t] for im, t in zip(world.data.images, take)])
    y = np.concatenate([lb[:t] for lb, t in zip(world.data.labels, take)])
    seg = np.repeat(np.arange(world.n), take)
    losses = sample_losses(p, x, y, dtype)
    tot = jax.ops.segment_sum(losses, jnp.asarray(seg),
                              num_segments=world.n)
    return (tot / jnp.maximum(jnp.asarray(take, dtype), 1)).astype(F32)


@functools.partial(jax.jit, static_argnames=("epochs", "batch", "steps",
                                             "lr", "dtype"))
def _local_sgd(p, images, labels, n_valid, key, *, epochs, batch, steps,
               lr, dtype):
    """Eq. 1: ``epochs`` passes, each over a fresh permutation of the
    vehicle's rows in ``steps`` batches; padding rows carry no loss."""
    cap = images.shape[0]
    p = ref.cast(p, dtype)
    images = images.astype(dtype)

    def loss(q, x, y, m):
        return (nll(forward(q, x), y) * m).sum() / jnp.maximum(m.sum(), 1)

    def epoch(q, ekey):
        perm = jax.random.permutation(ekey, cap)
        x, y = images[perm], labels[perm]
        m = (perm < n_valid).astype(dtype)

        def step(q, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * batch, batch)
            g = jax.grad(loss)(q, sl(x), sl(y), sl(m))
            return jax.tree.map(lambda a, b: a - jnp.asarray(lr, dtype) * b,
                                q, g), None

        return jax.lax.scan(step, q, jnp.arange(steps))[0], None

    return jax.lax.scan(epoch, p, jax.random.split(key, epochs))[0]


def local_train(world: ref.World, conf: Dict, p, i: int, rnd: int,
                dtype=F32):
    """Eq. 1 on vehicle ``i`` from the global ``p``, with its round key."""
    sim = conf["sim"]
    images = world.data.images[i]
    cap = images.shape[0]
    return _local_sgd(p, images, world.data.labels[i],
                      jnp.int32(world.n_valid[i]),
                      ref.train_key(world, rnd, i),
                      epochs=sim["local_epochs"],
                      batch=min(sim["batch_size"], cap),
                      steps=max(1, cap // sim["batch_size"]),
                      lr=sim["lr"], dtype=dtype)


def fit_loss(world: ref.World, ids: Sequence[int], p) -> float:
    """Mean loss of ``p`` over the valid rows of vehicles ``ids``."""
    x = np.concatenate([world.data.images[i][:world.n_valid[i]]
                        for i in ids])
    y = np.concatenate([world.data.labels[i][:world.n_valid[i]]
                        for i in ids])
    return float(np.mean(np.asarray(sample_losses(p, x, y), np.float64)))


def trained_leaves(p) -> List[np.ndarray]:
    """Every leaf of the CNN trains."""
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(p)]


# --------------------------------------------------------------------------
# required work, counted from shapes
# --------------------------------------------------------------------------

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


def work(conf: Dict, world: ref.World, rounds: int,
         trained_ids: Sequence[int]) -> Dict:
    """The window's required work: the probe and the accuracy read in
    each of ``rounds``, and local SGD for each entry of ``trained_ids``
    (a vehicle, once for every round that trained it)."""
    s = conf["sim"]
    probe = probe_work(world.n_valid, s["probe_samples"])
    return {"probe_flops": probe["flops"] * rounds,
            "probe_bytes": probe["bytes"] * rounds,
            "train_flops": train_flops(world.n_valid[list(trained_ids)],
                                       s["local_epochs"]),
            "eval_flops": eval_flops(len(world.data.test_labels)) * rounds}
