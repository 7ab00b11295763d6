"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload table3.sync --seed 7 \\
        --seconds 30 --trace 0

from the checkout root.  Prints progress and the compared numbers on
standard error and, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; ``compared`` comes last.  Exits
with 2 and prints no result when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # import the benchmark and the program from this checkout only
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench" / "tpu_logs"))
    try:
        from benchmarks.chip import cells, harness
        resolved = cells.resolve(args.workload, ROOT)
        from repro.launch.cache import enable_jit_cache, resolve_cache_dir
    except (ImportError, OSError, KeyError) as e:
        print(f"run: cannot load the benchmark or the program: {e!r}",
              file=sys.stderr)
        return 2
    try:
        harness.device_block(resolved["cell"]["chips"])
    except harness.NoChip as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    # the cache lives at a fixed path inside the checkout, so only the
    # first run of a cell in a checkout compiles; it keeps every program
    # of a cell (a size cap would evict the cohort trainers between runs)
    import jax
    enable_jit_cache(resolve_cache_dir(str(ROOT / ".jit-cache")))
    jax.config.update("jax_compilation_cache_max_size", -1)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), ROOT, resolved=resolved)
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
