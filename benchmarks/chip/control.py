"""Readings for the limits of ``correct``: the program's numbers, the
control's and those of planted faults, over many seeds in one process.

    python3 benchmarks/chip/control.py --workload table3.sync \\
        --seeds 11,12,13 --seconds 3 [--out FILE]

For each seed: the cell's set-up and a short window at its own load,
then every kept round compared with the reference as the program
produced it (the lower readings), and again with each of these in the
program's place (the upper readings):

- ``control``: the reference run whole at the configuration's
  ``control`` precision, the next below the one it states (float32 at
  ``high``, three bfloat16 passes, for float32 at ``highest``);
- ``half_batch``: the program's round with its trained params replaced
  by the reference's local SGD on the first half of each survivor's
  valid rows (each batch's other rows left out, the mean over the
  rest);
- ``unchanged``: the program's round with the params that entered it.

One JSON line per seed, each reading with its verdict under the
configuration's limits; the limits are set between the two kinds of
reading (``PERF.md``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(world, conf, outs, produce) -> dict:
    """The verdict on each kept round with ``produce(rnd, p_in, out)``
    in the program's place: the worst numbers over the rounds beside
    the configuration's limits."""
    import jax
    from benchmarks.chip import check
    per = []
    for rnd, o in sorted(outs.items()):
        p_in = jax.device_put(o["p_in"])
        got = produce(rnd, p_in, o)
        with jax.default_matmul_precision("highest"):
            nums = check.compare_round(world, conf, rnd, p_in, got)
        nums["replay"] = 0
        per.append(nums)
    return check.verdict(check.worst(per), conf["limits"])


def control(world, conf):
    """The whole round by the reference at the control precision."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip import reference as ref
    ctl = conf["control"]
    dtype = getattr(jnp, ctl["dtype"])

    def produce(rnd, p_in, o):
        with jax.default_matmul_precision(ctl["matmul_precision"]):
            return ref.run_round(world, conf, p_in, rnd, dtype=dtype)
    return produce


def with_params(world, o, params) -> dict:
    """The program's round with ``params`` in place of its own, and the
    accuracy read from them."""
    import jax
    from benchmarks.chip import reference as ref
    with jax.default_matmul_precision("highest"):
        count = ref.count_correct(params, world.test_images,
                                  world.test_labels)[0]
    return dict(o, params=params, count=count)


def half_batch(world, conf):
    """The trainer fault: half of every survivor's rows left out."""
    import jax
    from benchmarks.chip import reference as ref
    half = dataclasses.replace(world, n_valid=world.n_valid // 2)

    def produce(rnd, p_in, o):
        with jax.default_matmul_precision("highest"):
            params = ref.fedavg_round(half, conf, p_in, o["survivors"], rnd)
        return with_params(world, o, params)
    return produce


def unchanged(world, conf):
    """The fault of a round that returns its state unchanged."""
    return lambda rnd, p_in, o: with_params(world, o, p_in)


FAULTS = {"control": control, "half_batch": half_batch,
          "unchanged": unchanged}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from benchmarks.chip import cells, harness
    from repro.launch.cache import enable_jit_cache, resolve_cache_dir
    resolved = cells.resolve(args.workload, ROOT)
    try:
        harness.device_block(resolved["cell"]["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    import jax
    enable_jit_cache(resolve_cache_dir(str(ROOT / ".jit-cache")))
    jax.config.update("jax_compilation_cache_max_size", -1)
    conf = resolved["config"]
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.measure(resolved, seed, args.seconds, False, ROOT)
        got = harness.collect(run)
        prog = harness.check_rounds(got["world"], conf, got["outs"],
                                    conf["limits"])
        line = {"workload": args.workload, "seed": seed,
                "rounds": prog["rounds"],
                "program": {"correct": prog["correct"],
                            "compared": prog["compared"]}}
        for name, make in FAULTS.items():
            line[name] = readings(got["world"], conf, got["outs"],
                                  make(got["world"], conf))
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
