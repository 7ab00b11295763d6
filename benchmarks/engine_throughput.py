"""Round-engine throughput: grouped vmapped engine vs the single-stack
batched engine vs the per-client loop.

Two claims are measured:

- ISSUE 1 (updated): the batched engine is faster per round than the
  reference loop engine at >= 20 clients on CPU.  (PR 1 measured >= 2x
  against a loop that padded every client to the max capacity; the loop
  baseline now also trains at per-group caps, so the gap is smaller —
  the honest comparison.)
- ISSUE 2: on a quantity-skewed Table-3-shaped profile, the
  capacity-grouped engine beats the single uniform-capacity stack
  (``uniform_capacity=True``), because small-capacity cohort members
  train their own few steps per epoch instead of the 4500-sample group's
  mostly-masked step count.

Default profile: a Table-3-shaped fleet scaled to ~100 vehicles (12
data-rich, the rest data-poor).  ``REPRO_BENCH_FULL=1`` switches to the
true Table-3 profile (30 vehicles, 12x4500 + 18x45) and drops the loop
engine (untimeable on CPU at cap 4500).  Every engine gets warm-up
rounds (jit compile excluded — steady state is what Table-3-scale sweeps
pay for), then is timed over the remaining rounds.

Fairness note: all engines run the SAME semantics (required for parity),
including the PR-1 XLA:CPU fixes (reshape pool, loop unrolling, matmul
shuffle).  The loop baseline trains each client at its capacity group's
cap, like the grouped engine — the uniform-stack engine is the one
paying the padding bill.
"""
from __future__ import annotations

import os
import time
from typing import List

from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.fl.client import _SCAN_UNROLL, local_train_batch

FULL = os.environ.get("REPRO_BENCH_FULL", "") == "1"

if FULL:                       # true Table 3: 12 x 4500 + 18 x 45
    N_CLIENTS = 30
    PART = dict(big_clients=12, big_quantity=4500, small_quantity=45)
    SAMPLES_PER_CLASS = 7000   # no-dup partition demand is ~5580/class
                               # after the train/test split; keep real
                               # headroom so a seed change can't raise
    PROBE = 256
    N_CENTRAL = 6
    WARMUP_ROUNDS, TIMED_ROUNDS = 1, 2
    ENGINES = ("uniform", "grouped")
else:                          # Table-3-shaped, scaled to CI budget
    N_CLIENTS = 96
    PART = dict(big_clients=12, big_quantity=200, small_quantity=45)
    SAMPLES_PER_CLASS = 800
    PROBE = 200
    N_CENTRAL = 10
    WARMUP_ROUNDS, TIMED_ROUNDS = 2, 3
    ENGINES = ("loop", "uniform", "grouped")

# benchmark label -> (RunConfig.engine, uniform_capacity)
_VARIANTS = {"loop": ("loop", False),
             "uniform": ("batched", True),
             "grouped": ("batched", False)}


def _sim(variant: str) -> FLSimulation:
    engine, uniform = _VARIANTS[variant]
    part = PartitionConfig(n_clients=N_CLIENTS, classes_per_client=9,
                           **PART)
    # scheme="random": the engine comparison wants cohorts whose big/small
    # mix mirrors the fleet (18 of 30 Table-3 vehicles are data-poor);
    # eval-ranked schemes bias cohorts towards big clients and turn this
    # into a selection-quality bench.  All variants draw the identical
    # selection sequence, so the comparison stays apples-to-apples.
    cfg = FLSimConfig(scheme="random", local_epochs=1,
                      n_clients_central=N_CENTRAL, probe_samples=PROBE,
                      samples_per_class=SAMPLES_PER_CLASS,
                      uniform_capacity=uniform, partition=part,
                      mobility=MobilityConfig(n_vehicles=N_CLIENTS, seed=0),
                      seed=0)
    return FLSimulation(cfg, run=RunConfig(engine=engine))


def bench_engine_throughput() -> List[str]:
    rows = []
    per_round = {}
    profile = (f"n_clients={N_CLIENTS};big={PART['big_quantity']};"
               f"small={PART['small_quantity']};timed_rounds={TIMED_ROUNDS}")
    for variant in ENGINES:
        sim = _sim(variant)
        # warmup() pre-executes the trainer once per cohort bucket: cheap
        # insurance at the scaled profile, but at cap 4500 each bucket
        # execution costs a full round's train time (the 225-step scan is
        # execution-bound — its compile is seconds), so FULL relies on
        # the warm-up rounds to compile organically.  A timed FULL round
        # that draws an unseen bucket size pays one scan-trainer compile
        # (~1-10% of a round); acceptable against 40+ min of eager
        # warmup executions.
        if not FULL:
            sim.warmup()               # compile cohort buckets up front
        for r in range(WARMUP_ROUNDS):
            sim.run_round(r)
        t0 = time.perf_counter()
        for r in range(WARMUP_ROUNDS, WARMUP_ROUNDS + TIMED_ROUNDS):
            sim.run_round(r)
        dt = (time.perf_counter() - t0) / TIMED_ROUNDS
        per_round[variant] = dt
        rows.append(f"engine_{variant}_round_s,{dt:.3f},{profile}")
    if "loop" in per_round:
        speedup = per_round["loop"] / max(per_round["grouped"], 1e-9)
        rows.append(f"engine_batched_speedup,{speedup:.2f},"
                    f"claim=batched beats the per-client loop (which now "
                    f"also trains at per-group caps)")
    grp = per_round["uniform"] / max(per_round["grouped"], 1e-9)
    rows.append(f"engine_grouped_speedup,{grp:.2f},"
                f"claim=capacity groups beat the uniform max-cap stack")
    return rows


_OVERLAP_CHILD = r"""
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys, time, json
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.launch.sweep import run_seed_group

overlap = sys.argv[1] == "overlap"
shape = sys.argv[2]                    # single | sweep

def cfg(scheme, classes, dist, seed):
    part = PartitionConfig(n_clients=32, big_clients=4, big_quantity=200,
                           small_quantity=45, classes_per_client=9,
                           seed=seed)
    return FLSimConfig(scheme="random", local_epochs=1,
                       n_clients_central=8, probe_samples=64,
                       samples_per_class=400, partition=part,
                       mobility=MobilityConfig(n_vehicles=32, seed=seed),
                       seed=seed)

rounds = 3
if shape == "single":
    sim = FLSimulation(cfg("random", 9, "uniform", 0))
    sim.warmup()
    sim.run(1, overlap=overlap)                 # compile prefix/metrics
    t0 = time.perf_counter()
    sim.run(rounds, overlap=overlap)
else:
    seeds = [0, 1, 2, 3]
    run_seed_group("random", 9, "uniform", seeds, 1, cfg_fn=cfg,
                   overlap=overlap)             # warm every seed's jits
    t0 = time.perf_counter()
    run_seed_group("random", 9, "uniform", seeds, rounds, cfg_fn=cfg,
                   overlap=overlap)
import jax
print(json.dumps({"round_s": (time.perf_counter() - t0) / rounds,
                  "platform": jax.devices()[0].platform}))
"""


def bench_round_overlap() -> List[str]:
    """ISSUE 5: the round-ahead scheduler vs the serial driver.

    Same rounds, same math (rows pinned identical in
    tests/test_probe_fuzzy.py) — the overlap driver enqueues round
    r+1's selection prefix right after round r's trainers, before any
    metric reads.  Each (variant, shape) cell runs in its OWN
    subprocess so neither side inherits the other's warm jit caches
    (a same-process comparison confounds compile reuse with overlap).

    Two shapes, both warmed before timing:

    - **single** sim: the dependency chain selection_{r+1} <- agg_r <-
      train_r is inherently serial and XLA:CPU drains one in-order
      execution stream, so a lone simulation can only hide the
      host-side dispatch gaps (~ms) — reported as the honest
      ~break-even baseline.
    - **sweep** cell (4 seeds — the scheduler's actual target): the
      serial driver resolves each seed's metrics/row between training
      dispatches, idling the device once per seed per round; the
      round-ahead driver enqueues all seeds' training and the next
      vmapped selection dispatch before any row resolve, so the device
      queue never drains while the host does per-seed bookkeeping.
      This is the wall-clock overlap claim (selection_{r+1}'s dispatch
      + cross-seed device work hide the per-seed host tails)."""
    import json as _json
    import subprocess as _sp
    import sys as _sys
    from pathlib import Path as _Path

    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "engine_overlap times child processes, and this process "
                f"already holds the {jax.default_backend()} backend; run "
                "it first or alone: python -m benchmarks.run "
                "engine_overlap")

    rows, per = [], {}
    src = str(_Path(__file__).resolve().parent.parent / "src")
    prev = os.environ.get("PYTHONPATH")
    env = {**os.environ,
           "PYTHONPATH": src + (os.pathsep + prev if prev else "")}
    for shape in ("single", "sweep"):
        for label in ("serial", "overlap"):
            proc = _sp.run([_sys.executable, "-c", _OVERLAP_CHILD, label,
                            shape], capture_output=True, text=True,
                           env=env, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(f"overlap child {shape}/{label} "
                                   f"failed:\n{proc.stderr[-2000:]}")
            got = _json.loads(proc.stdout.strip().splitlines()[-1])
            per[(shape, label)] = got["round_s"]
            rows.append(f"engine_{shape}_{label}_round_s,"
                        f"{got['round_s']:.3f},platform={got['platform']};"
                        f"n_clients=32;warm;"
                        f"round-ahead={label == 'overlap'};"
                        f"{'4 seeds' if shape == 'sweep' else '1 sim'}")
    single = per[("single", "serial")] / per[("single", "overlap")]
    rows.append(f"engine_overlap_single_ratio,{single:.3f},"
                f"one sim on one in-order CPU stream: only host dispatch "
                f"gaps to hide — informational, not gated")
    hidden = per[("sweep", "serial")] - per[("sweep", "overlap")]
    speedup = per[("sweep", "serial")] / per[("sweep", "overlap")]
    rows.append(f"engine_overlap_hidden_s,{hidden:.3f},"
                f"per-round wall hidden in a 4-seed sweep cell: device "
                f"queue stays full through per-seed metric resolves")
    rows.append(f"engine_overlap_speedup,{speedup:.3f},"
                f"claim=round-ahead scheduler hides selection dispatch + "
                f"cross-seed work under the per-seed round tails")
    return rows


def bench_trainer_unroll() -> List[str]:
    """ISSUE 3 satellite: chunk-unrolling the ``lax.scan`` step loop.

    Step counts past ``_UNROLL_LIMIT`` (the Table-3 cap-4500 trainer:
    225 steps/epoch) pay the XLA:CPU while-loop overhead per iteration;
    ``lax.scan(..., unroll=_SCAN_UNROLL)`` amortizes the loop overhead
    over straight-line blocks.  Measured here on a cap-1600 2-client
    cohort (80 steps/epoch — scan path, CI-affordable): before = unroll
    1 (the pre-ISSUE-3 scan), after = the engine default (~1.1x on the
    2-core dev box — the conv-grad body dominates, so the win is real
    but modest).  Math is identical — same steps, same order."""
    import jax
    import jax.numpy as jnp
    from repro.configs.mnist_cnn import CONFIG as CNN_CFG
    from repro.models.cnn import init_cnn

    c, cap, batch = 2, 1600, 20                 # 80 steps > _UNROLL_LIMIT
    key = jax.random.PRNGKey(0)
    params = init_cnn(key, CNN_CFG)
    images = jax.random.normal(key, (c, cap, 28, 28, 1))
    labels = jnp.zeros((c, cap), jnp.int32)
    n_valid = jnp.full((c,), cap, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), c)

    rows, per_call = [], {}
    profile = f"c={c};cap={cap};steps={cap // batch};epochs=1"
    for label, unroll in (("scan", 1), ("chunked", _SCAN_UNROLL)):
        kw = dict(epochs=1, batch_size=batch, steps_per_epoch=cap // batch,
                  lr=0.05, scan_unroll=unroll)
        out, _ = local_train_batch(params, images, labels, n_valid, keys,
                                   **kw)                  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out, _ = local_train_batch(params, images, labels, n_valid, keys,
                                   **kw)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        per_call[label] = dt
        rows.append(f"trainer_{label}_call_s,{dt:.3f},"
                    f"{profile};unroll={unroll}")
    speedup = per_call["scan"] / max(per_call["chunked"], 1e-9)
    rows.append(f"trainer_unroll_speedup,{speedup:.2f},"
                f"claim=chunk-unrolled scan beats the while-loop slow path")
    return rows
