"""``chip_smoke.py`` on the CPU: it refuses to report a result without a
TPU, and its phase functions run end to end at the CPU-budget profile
with the jnp impl (the chip run is the one at Table 3 size)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert "no tpu device" in err and "platform 'cpu'" in err
    assert '"ok"' not in out


def test_phases_on_cpu_fast_profile(tmp_path, capsys):
    """Phases (a)-(e) at the fast profile for one round: fl_sim rounds
    through the CLI entry point, the impl comparison (jnp against
    itself: no kernel on the CPU) and the CPU comparison."""
    dev = chip_smoke.device_check(platform="cpu")
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    fast = ("--jit-cache-dir", "none")
    chip_smoke.one_chip(tmp_path, rounds=1, profile=fast, train_profile=fast,
                        impl="jnp", want_kernel=False)
    out = capsys.readouterr().out
    for tag in ("(b) dcs", "(b) ccs-fuzzy", "(c) jnp vs jnp",
                "masks identical True", "(d) chip vs cpu",
                "masks agree True"):
        assert tag in out, tag
    assert "largest relative evaluation gap 0.000e+00" in out
    for scheme in chip_smoke.SCHEMES:
        path = tmp_path / "profile" / f"{scheme}.json"
        assert len(json.loads(path.read_text())[scheme]) == 1
    rows = json.loads((tmp_path / "train" / "dcs.json").read_text())["dcs"]
    assert rows[0]["n_aggregated"] > 0


_FOUR = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())
import chip_smoke
cfg = chip_smoke.fleet1k_config(n=64, big=4, big_q=60, small_q=24,
                                local_epochs=1)
cfg.probe_samples = 64
chip_smoke.four_chips(rounds=2, shards=4, cfg=cfg)
"""


def test_four_chips_on_emulated_devices():
    """The ``--four-chips`` path on 4 forced CPU devices at a 64-vehicle,
    1-epoch size: carried and single-round sharded runs against the
    single-device gather run."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    proc = subprocess.run([sys.executable, "-c", _FOUR], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = proc.stdout
    for tag in ("round 0 carried: masks identical True",
                "round 1 carried: masks identical True",
                "round 1 step: masks identical True",
                "devices used by the sharded rounds: [0, 1, 2, 3]"):
        assert tag in out, tag
