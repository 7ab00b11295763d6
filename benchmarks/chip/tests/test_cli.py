"""The command refuses to measure without a chip, and without the
program beside it, and prints no result either way."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip import cells


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "table3.sync", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(cells.ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.HERE, tmp_path / "benchmarks" / "chip")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
