"""Multi-process ``clients`` mesh (ISSUE 9: emulated multi-host fleet).

Spawns 2 coordinated CPU jax processes (gloo collectives) per test —
the same wiring ``--multihost 2`` uses — and checks:

- distributed init + a cross-process psum over the global clients mesh;
- the windowed sharded prefix under a 2-process mesh emits masks
  bit-identical to the same simulation in a single process;
- a tiny end-to-end ``fl_sim --multihost 2`` launch completes and
  writes output from process 0 only.

Every test gracefully skips when the runtime cannot form the
2-process group (no gloo CPU collectives in the jaxlib build, or the
coordination service cannot bind) — the capability probe runs once per
session and is itself a spawned pair of processes.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_PROBE = r"""
import sys
from repro.launch.mesh import init_distributed
coord, procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
init_distributed(coord, procs, pid, local_devices=2)
import jax
assert jax.process_count() == procs, jax.process_count()
assert len(jax.devices()) == 2 * procs, len(jax.devices())
print("PROBE_OK", pid)
"""


def _spawn_pair(child_src: str, extra_args=(), timeout=600):
    """Run ``child_src`` as 2 coordinated processes (argv: coord procs
    pid [extra...]); returns (rc, stdout_of_proc0, stderr_both)."""
    from repro.launch.multihost import free_port
    coord = f"127.0.0.1:{free_port()}"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)           # children pick their own count
    procs = [subprocess.Popen(
        [sys.executable, "-c", child_src, coord, "2", str(pid),
         *map(str, extra_args)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=timeout))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    rc = max(p.returncode for p in procs)
    return rc, outs[0][0], "\n".join(o[1] for o in outs)


@pytest.fixture(scope="session")
def multihost_available():
    rc, out, err = _spawn_pair(_PROBE, timeout=300)
    if rc != 0 or "PROBE_OK" not in out:
        pytest.skip(f"2-process jax runtime unavailable: {err[-800:]}")
    return True


@pytest.mark.slow
def test_distributed_psum_across_processes(multihost_available):
    child = r"""
import sys
from repro.launch.mesh import init_distributed, make_multihost_clients_mesh
coord, procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
init_distributed(coord, procs, pid, local_devices=2)
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = make_multihost_clients_mesh(4)
x = np.arange(8, dtype=np.float32)
xs = jax.device_put(x, NamedSharding(mesh, P("clients")))
tot = jax.jit(jax.shard_map(
    lambda v: jax.lax.psum(v.sum(), "clients"),
    mesh=mesh, in_specs=P("clients"), out_specs=P()))(xs)
assert float(jax.device_get(tot)) == float(x.sum()), tot
print("PSUM_OK", pid)
"""
    rc, out, err = _spawn_pair(child)
    assert rc == 0, f"psum child failed:\n{err[-3000:]}"
    assert "PSUM_OK" in out


@pytest.mark.slow
def test_windowed_prefix_parity_across_processes(multihost_available):
    """The tentpole's 2-process acceptance: the windowed sharded prefix
    on a mesh spanning 2 jax processes produces the same masks as the
    identical simulation run single-process (which is itself pinned to
    the dense election elsewhere)."""
    child = r"""
import sys
coord, procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
multi = procs > 0
if multi:
    from repro.launch.mesh import init_distributed
    init_distributed(coord, procs, pid, local_devices=2)
else:
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.launch.mesh import make_clients_mesh, \
    make_multihost_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding

N = 10
cfg = FLSimConfig(
    scheme="dcs", n_rounds=2, local_epochs=1, samples_per_class=260,
    probe_samples=64, seed=0,
    partition=PartitionConfig(n_clients=N, big_clients=3,
                              big_quantity=120, small_quantity=40,
                              classes_per_client=9, seed=0),
    mobility=MobilityConfig(n_vehicles=N, seed=0))
mesh = make_multihost_clients_mesh(4) if multi else make_clients_mesh(4)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    sim = FLSimulation(cfg, run=RunConfig(elect="windowed"))
    masks = []
    for r in range(2):
        host = sim.resolve_elect_overflow(
            r, jax.device_get(sim.selection_state(r)))
        masks.append(np.asarray(host["mask"]).tolist())
print("MASKS" + json.dumps(masks))
"""
    rc, out, err = _spawn_pair(child)
    assert rc == 0, f"2-process prefix child failed:\n{err[-3000:]}"
    multi_masks = _extract_masks(out)

    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    single = subprocess.run(
        [sys.executable, "-c", child, "unused", "0", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert single.returncode == 0, \
        f"single-process reference failed:\n{single.stderr[-3000:]}"
    assert multi_masks == _extract_masks(single.stdout), \
        "2-process windowed masks diverge from single-process"


def _extract_masks(out: str):
    for line in out.splitlines():
        if line.startswith("MASKS"):
            return json.loads(line[len("MASKS"):])
    raise AssertionError(f"no MASKS line in output: {out[-500:]!r}")


@pytest.mark.slow
def test_fl_sim_multihost_launch(multihost_available, tmp_path):
    """End-to-end ``fl_sim --multihost 2``: the parent re-spawns itself,
    the children form the mesh, and process 0 writes the output file."""
    out = tmp_path / "mh.json"
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.fl_sim", "--scheme", "dcs",
         "--rounds", "1", "--mesh", "clients=4", "--multihost", "2",
         "--elect", "windowed", "--jit-cache-dir", "none",
         "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1200)
    assert proc.returncode == 0, \
        f"fl_sim --multihost failed:\n{proc.stderr[-3000:]}\n" \
        f"{proc.stdout[-1000:]}"
    data = json.loads(out.read_text())
    assert "dcs" in data and len(data["dcs"]) == 1
    assert "2 processes" in proc.stdout


def test_multi_process_modes_refuse_off_cpu(monkeypatch, tmp_path):
    """``--multihost`` and ``sweep --workers > 1`` start several jax
    processes on one host; off the CPU backend (a chip belongs to one
    process) both refuse before starting any."""
    import jax

    from repro.launch import multihost, sweep
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="gives each chip to one process"):
        multihost.spawn_multihost("repro.launch.fl_sim", [], 2)
    with pytest.raises(SystemExit, match="--workers 2"):
        sweep.main(["--workers", "2", "--schemes", "dcs", "--seeds", "1",
                    "--rounds", "1", "--out", str(tmp_path / "s.csv")])
    assert not list(tmp_path.iterdir())
