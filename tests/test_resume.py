"""Preemption-safe resume (ISSUE 10): kill-at-round-r + resume must
reproduce the uninterrupted trajectory's rows, masks and params
**bit-identically** — for the serial driver, the round-ahead overlapped
scheduler, the event-driven server under churn + weighted staleness +
cadence, the sweep's vmapped seed groups, and a forced 4-device clients
mesh.  Plus the sweep-grid recovery contract: completed groups are
skipped verbatim and the final CSV is byte-identical to an
uninterrupted run's.
"""
import json
import os
import subprocess
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.launch.sweep import (CSV_COLUMNS, completed_job_rows,
                                parse_csv_rows, rows_to_csv,
                                run_seed_group, sweep)
from repro.train.checkpoint import RoundCheckpointer

REPO = Path(__file__).resolve().parent.parent

N_CLIENTS = 10


def _cfg(scheme: str = "ccs-fuzzy", seed: int = 0, n: int = N_CLIENTS,
         **kw) -> FLSimConfig:
    return FLSimConfig(
        scheme=scheme, n_rounds=4, local_epochs=1,
        samples_per_class=260, probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=n, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=n, seed=seed), **kw)


def _leaves(sim):
    return [np.asarray(x) for x in jax.tree.leaves(sim.params)]


def _assert_params_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _kill_resume_parity(tmp_path, run=None, overlap=None, rounds=4,
                        kill_after=2):
    """Run uninterrupted; then run ``kill_after`` rounds with a
    checkpointer, throw the process state away (fresh simulation) and
    resume to the end — rows and final params must match exactly."""
    full = FLSimulation(_cfg(), run=run)
    rows_full = full.run(rounds, overlap=overlap)

    ck = RoundCheckpointer(str(tmp_path / "ck"))
    part = FLSimulation(_cfg(), run=run)
    part.run(kill_after, overlap=overlap, checkpointer=ck)

    res = FLSimulation(_cfg(), run=run)
    rows_res = res.run(rounds, overlap=overlap, checkpointer=ck,
                       resume=True)
    assert rows_res == rows_full
    _assert_params_equal(_leaves(full), _leaves(res))
    np.testing.assert_array_equal(np.asarray(full.last_mask),
                                  np.asarray(res.last_mask))
    np.testing.assert_array_equal(full.participation, res.participation)


def test_sync_resume_parity(tmp_path):
    """Serial driver: resume from round 2 of 4 is bit-identical."""
    _kill_resume_parity(tmp_path)


def test_overlap_resume_parity(tmp_path):
    """Round-ahead pipelined scheduler: the dispatch a kill threw away
    is re-issued identically from the restored params."""
    _kill_resume_parity(tmp_path, overlap=True)


class _Stop(Exception):
    pass


class _SaveThenStop(RoundCheckpointer):
    """Saves round ``stop`` and then raises, as the chip benchmark's
    window close does: round-ahead, round ``stop + 1``'s cohort is
    dispatched by then and not yet committed."""

    def __init__(self, directory, stop):
        super().__init__(directory)
        self.stop = stop

    def save_round(self, rnd, state, extra=None):
        super().save_round(rnd, state, extra)
        if rnd == self.stop:
            raise _Stop(rnd)


def test_overlap_resume_after_uncommitted_cohort(tmp_path):
    """Round-ahead, stopped from round 1's checkpoint after round 2's
    cohort was dispatched: the simulation still holds round 1's params,
    mask and participation, and the resumed rows and params are
    bit-identical to an uninterrupted run."""
    ref = FLSimulation(_cfg())
    ref.run(2, overlap=False)
    full = FLSimulation(_cfg())
    rows_full = full.run(4, overlap=True)
    assert rows_full[2]["n_aggregated"] > 0      # round 2 trains

    ck = _SaveThenStop(str(tmp_path / "ck"), stop=1)
    part = FLSimulation(_cfg())
    with pytest.raises(_Stop):
        part.run(4, overlap=True, checkpointer=ck)
    assert part.counters.rounds == 3             # round 2's fence read
    _assert_params_equal(_leaves(part), _leaves(ref))
    np.testing.assert_array_equal(part.last_mask, ref.last_mask)
    np.testing.assert_array_equal(part.participation, ref.participation)

    res = FLSimulation(_cfg())
    rows_res = res.run(4, overlap=True,
                       checkpointer=RoundCheckpointer(str(tmp_path / "ck")),
                       resume=True)
    assert rows_res == rows_full
    _assert_params_equal(_leaves(full), _leaves(res))
    np.testing.assert_array_equal(full.last_mask, res.last_mask)
    np.testing.assert_array_equal(full.participation, res.participation)


def test_event_resume_parity(tmp_path):
    """Event-driven server under churn + weighted staleness + a cadence
    faster than the round period: the pending-tick pool crosses the
    kill point and must be restored exactly."""
    run = RunConfig(server="event", churn_rate=0.3,
                    staleness="weighted", staleness_lambda=1.0,
                    agg_cadence_s=20.0)
    _kill_resume_parity(tmp_path, run=run)


def test_checkpoint_dir_runconfig_path(tmp_path):
    """The --checkpoint-dir/--resume contract through RunConfig alone:
    cadence honoured on disk, resume idempotent at end-of-run."""
    d = str(tmp_path / "ck")
    rows = FLSimulation(
        _cfg(), run=RunConfig(checkpoint_dir=d, checkpoint_every=2)
    ).run(4)
    assert RoundCheckpointer(d).rounds_on_disk() == [1, 3]
    # resume after the final round: nothing to run, rows identical
    again = FLSimulation(
        _cfg(), run=RunConfig(checkpoint_dir=d, checkpoint_every=2,
                              resume=True)).run(4)
    assert again == rows


def test_runconfig_checkpoint_validation():
    with pytest.raises(ValueError, match="checkpoint_every"):
        RunConfig(checkpoint_dir="/tmp/x", checkpoint_every=0).resolved()
    with pytest.raises(ValueError, match="resume"):
        RunConfig(resume=True).resolved()


def test_restore_rejects_mismatched_config():
    """A snapshot from a different seed or fleet size must be refused,
    never silently loaded into the wrong simulation."""
    state = FLSimulation(_cfg(seed=0)).capture_state()
    with pytest.raises(ValueError, match="PRNG base"):
        FLSimulation(_cfg(seed=1)).restore_state(state)
    with pytest.raises(ValueError, match="fleet"):
        FLSimulation(_cfg(n=12)).restore_state(state)


# --------------------------------------------------------------------------
# sweep-grid recovery
# --------------------------------------------------------------------------

def _tiny(scheme, classes, dist, seed):
    return FLSimConfig(
        scheme=scheme, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=10, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=classes, seed=seed),
        mobility=MobilityConfig(n_vehicles=10, distribution=dist,
                                seed=seed))


@pytest.mark.slow
def test_run_seed_group_resume_parity(tmp_path):
    """A seed group restarted from its round checkpoints finishes with
    the same rows as the uninterrupted group (vmapped prefix path)."""
    ref = run_seed_group("dcs", 9, "uniform", [0, 1], 3, cfg_fn=_tiny)
    d = str(tmp_path / "grp")
    run_seed_group("dcs", 9, "uniform", [0, 1], 2, cfg_fn=_tiny,
                   checkpoint_dir=d)          # "killed" after round 1
    got = run_seed_group("dcs", 9, "uniform", [0, 1], 3, cfg_fn=_tiny,
                         checkpoint_dir=d, resume=True)
    assert got == ref


@pytest.mark.slow
def test_sweep_resume_skips_completed_byte_identical(tmp_path):
    """Grid recovery: with one job's rows already in the partial CSV,
    resume skips that group, reruns the rest, and the final CSV is
    byte-identical to the uninterrupted run's."""
    grid = dict(schemes=("dcs", "random"), classes_list=(9,),
                distributions=("uniform",), seeds=(0, 1), rounds=2,
                cfg_fn=_tiny)
    ref_csv = rows_to_csv(sweep(**grid))
    partial = [r for r in parse_csv_rows(ref_csv)
               if r["scheme"] == "dcs"]        # job 1 of 2 completed
    out = tmp_path / "sweep.csv"
    out.write_text(rows_to_csv(partial))
    logs = []
    rows = sweep(**grid, out_path=str(out),
                 checkpoint_dir=str(tmp_path / "ck"), resume=True,
                 log=logs.append)
    assert rows_to_csv(rows) == ref_csv
    assert out.read_text() == ref_csv          # incremental write landed
    assert any("skipping completed group" in m for m in logs)


def _fake_row(seed=0, rnd=0, scheme="dcs", **over):
    row = {c: 0.0 for c in CSV_COLUMNS}
    row.update(round=rnd, scheme=scheme, seed=seed, classes_per_client=9,
               distribution="uniform", accuracy=0.5, n_selected=3,
               n_aggregated=3, n_straggler=0, n_active=10,
               rounds_behind_hist="3|0|0")
    row.update(over)
    return row


def test_parse_csv_foreign_header_rejected():
    assert parse_csv_rows("a,b,c\n1,2,3\n") is None
    assert parse_csv_rows("") is None


def test_parse_csv_drops_torn_tail():
    """A torn final line (as left by a non-atomic writer) is dropped
    with a warning; intact rows survive and re-format byte-identically."""
    rows = [_fake_row(seed=s, rnd=r) for s in (0, 1) for r in (0, 1)]
    text = rows_to_csv(rows)
    torn = text[:len(text) - 25]               # cut into the last row
    with pytest.warns(RuntimeWarning, match="torn tail"):
        parsed = parse_csv_rows(torn)
    assert len(parsed) == len(rows) - 1
    # format idempotency: the surviving rows re-emit byte-identically
    assert rows_to_csv(parsed) == rows_to_csv(rows[:-1])


def test_completed_job_rows_requires_full_coverage():
    run = RunConfig().resolved()
    jobs = [(("dcs", 9, "uniform"), run)]
    rows = [_fake_row(seed=s, rnd=r) for s in (0, 1) for r in (0, 1)]
    done = completed_job_rows(rows, jobs, seeds=(0, 1), rounds=2)
    assert len(done) == 1 and len(next(iter(done.values()))) == 4
    # a grown grid (more rounds or seeds) invalidates completion
    assert completed_job_rows(rows, jobs, seeds=(0, 1), rounds=3) == {}
    assert completed_job_rows(rows, jobs, seeds=(0, 1, 2), rounds=2) == {}
    # a shrunk grid must not leak out-of-range rows into the final CSV
    done = completed_job_rows(rows, jobs, seeds=(0, 1), rounds=1)
    assert {r["round"] for r in next(iter(done.values()))} == {0}


# --------------------------------------------------------------------------
# forced 4-device clients mesh
# --------------------------------------------------------------------------

_MESH_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import sys
import numpy as np
import jax
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding
from repro.train.checkpoint import RoundCheckpointer

ckdir = sys.argv[1]
N = 10                                   # not divisible by 4

def cfg(seed=0):
    return FLSimConfig(
        scheme="dcs", n_rounds=3, local_epochs=1, samples_per_class=260,
        probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N, seed=seed))

mesh = make_clients_mesh(4)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    full = FLSimulation(cfg())
    assert full.client_mesh is not None and full.n_shards == 4
    rows_full = full.run(3)

    ck = RoundCheckpointer(ckdir)
    part = FLSimulation(cfg())
    part.run(2, checkpointer=ck)          # killed after round 1

    res = FLSimulation(cfg())
    rows_res = res.run(3, checkpointer=ck, resume=True)
    assert rows_res == rows_full, "resume rows diverge on clients mesh"
    for a, b in zip(jax.tree.leaves(full.params),
                    jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(full.last_mask),
                                  np.asarray(res.last_mask))
print(json.dumps({"ok": True,
                  "n_sel": int(sum(r["n_selected"] for r in rows_full))}))
"""


@pytest.mark.slow
def test_resume_parity_on_forced_mesh(tmp_path):
    """The same kill-and-resume pin on a forced 4-device clients mesh:
    the restored params re-enter the shard_map'd prefix and the psum'd
    FedAvg bit-identically (subprocess, like tests/test_sharding.py)."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_CHILD, str(tmp_path / "ck")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1500)
    assert proc.returncode == 0, \
        f"mesh resume parity child failed:\n{proc.stderr[-4000:]}"
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    assert data["ok"] and data["n_sel"] > 0
