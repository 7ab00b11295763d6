"""Host spans, device scopes and round counters (``repro.fl.trace``).

Every driver (serial, round-ahead, event server, seed-group sweep)
writes one ``fl.round`` step per round into a profiler session, with the
fence, cohort, dispatch and read spans of that round inside it; the
compiled prefix (single-device and sharded) keeps its stage scopes in
the HLO ``op_name``; FedAvg is an executable of its own name; the
counters count padded cohort slots and overflowed elections.
"""
import dataclasses
import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.fl import pipeline, trace
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig

REPO = Path(__file__).resolve().parent.parent

N_CLIENTS = 10
ROUNDS = 3
CHILD_SPANS = (trace.FENCE, trace.COHORT, trace.DISPATCH, trace.READ)


def _cfg(seed: int = 0, **kw) -> FLSimConfig:
    return FLSimConfig(
        scheme="dcs", n_rounds=ROUNDS, local_epochs=1,
        samples_per_class=260, probe_samples=64, seed=seed,
        partition=PartitionConfig(n_clients=N_CLIENTS, big_clients=3,
                                  big_quantity=120, small_quantity=40,
                                  classes_per_client=9, seed=seed),
        mobility=MobilityConfig(n_vehicles=N_CLIENTS, seed=seed), **kw)


def _host_spans(log_dir):
    """``(start, end, name, args)`` of every ``fl.*`` host event."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("fl."))
    return out


def _spans_by_round(spans):
    """Per ``fl.round`` step: the names of the spans inside it that
    carry its round number."""
    steps = sorted((a["step_num"], s, e) for s, e, n, a in spans
                   if n == trace.ROUND)
    got = {}
    for r, lo, hi in steps:
        got[r] = sorted(n for s, e, n, a in spans
                        if n != trace.ROUND and a.get("round") == r
                        and lo <= s and e <= hi)
    return got


def _profile(fn, tmp_path):
    fn()                                 # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        fn()
    return _host_spans(str(tmp_path))


def _spans_in_steps(spans):
    """Per ``fl.round`` step: ``(name, round)`` of every span inside it."""
    steps = sorted((a["step_num"], s, e) for s, e, n, a in spans
                   if n == trace.ROUND)
    return {r: sorted((n, a.get("round")) for s, e, n, a in spans
                      if n != trace.ROUND and lo <= s and e <= hi)
            for r, lo, hi in steps}


def test_round_ahead_spans(tmp_path):
    """Three round-ahead rounds: three ``fl.round`` steps.  Step r holds
    round r's fence, cohort and dispatch (the next round's prefix, then
    round r's eval), and the read and checkpoint of round r-1; the last
    step also reads and checkpoints its own round."""
    sim = FLSimulation(_cfg(), run=RunConfig(overlap_rounds=True))
    spans = _profile(lambda: sim.run(ROUNDS, resume=False), tmp_path)
    got = _spans_in_steps(spans)
    assert sorted(got) == list(range(ROUNDS))
    for r, names in got.items():
        want = [(trace.FENCE, r), (trace.COHORT, r), (trace.DISPATCH, r)]
        done = [r - 1] if r else []
        if r == ROUNDS - 1:
            done.append(r)
        want += [(n, d) for d in done for n in (trace.READ,
                                                trace.CHECKPOINT)]
        assert names == sorted(want), names
    ahead = {a["round"]: (a.get("prefix_round"), a.get("eval_round"))
             for s, e, n, a in spans if n == trace.DISPATCH}
    assert ahead == {0: (1, 0), 1: (2, 1), 2: (None, 2)}
    assert not [n for s, e, n, a in spans if n == trace.ELECT_RERUN]


class _Recorder:
    """A checkpointer that reads the simulation as the benchmark's round
    clock does: in ``due(r)`` the params and mask are round r's output,
    and the round trained when the params object changed."""

    def __init__(self, sim):
        self.sim, self.prev, self.seen = sim, sim.params, []

    def due(self, rnd):
        p = self.sim.params
        self.seen.append((rnd, jax.device_get(p),
                          np.array(self.sim.last_mask), p is not self.prev))
        self.prev = p
        return False


def test_round_ahead_checkpointer_sees_each_rounds_output():
    """In every ``due(r)`` of a round-ahead run the checkpointer reads
    the serial driver's round-r params and mask, by value, and the
    params-identity test flags the same rounds as trained.  A 10 s
    deadline leaves rounds 0 and 3 without a survivor."""
    seen = {}
    for overlap in (False, True):
        sim = FLSimulation(_cfg(deadline_s=10.0))
        rec = _Recorder(sim)
        sim.run(5, overlap=overlap, checkpointer=rec, resume=False)
        seen[overlap] = rec.seen
    assert [s[3] for s in seen[False]] == [False, True, True, False, True]
    for (rs, ps, ms, ts), (ro, po, mo, to) in zip(seen[False], seen[True],
                                                  strict=True):
        assert (rs, ts) == (ro, to)
        np.testing.assert_array_equal(ms, mo)
        for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(po)):
            np.testing.assert_array_equal(a, b)


def _ahead():
    sim = FLSimulation(_cfg(), run=RunConfig(overlap_rounds=True))
    return (lambda: sim.run(ROUNDS, resume=False)), sim.counters


def _serial():
    sim = FLSimulation(_cfg(), run=RunConfig(overlap_rounds=False))
    return (lambda: sim.run(ROUNDS, resume=False)), sim.counters


def _event(overlap):
    def make():
        sim = FLSimulation(_cfg(), run=RunConfig(
            server="event", staleness="weighted", staleness_lambda=1.0,
            overlap_rounds=overlap))
        return (lambda: sim.run(ROUNDS, resume=False)), sim.counters
    return make


def _sweep():
    from repro.launch.sweep import run_seed_group

    def tiny(scheme, classes, dist, seed):
        return _cfg(seed)

    counters = trace.RoundCounters()
    return (lambda: run_seed_group("dcs", 9, "uniform", [0, 1], ROUNDS,
                                   cfg_fn=tiny, overlap=True,
                                   counters=counters)), counters


DRIVERS = [_serial, _event(False), _event(True), _sweep]
DRIVER_IDS = ["serial", "event", "event-ahead", "sweep"]


@pytest.mark.parametrize("make", DRIVERS, ids=DRIVER_IDS)
def test_every_driver_emits_the_round_spans(make, tmp_path):
    """The serial driver, the event server (its own cohort path) both
    ways, and the seed-group sweep: one step per round holding the
    fence, cohort, dispatch and read spans (one of each per seed)."""
    run, _ = make()
    by_round = _spans_by_round(_profile(run, tmp_path))
    assert sorted(by_round) == list(range(ROUNDS))
    for names in by_round.values():
        assert set(CHILD_SPANS) <= set(names), names


@pytest.mark.parametrize("make,behind", [(_ahead, ROUNDS - 1)]
                         + [(m, 0) for m in DRIVERS],
                         ids=["round-ahead"] + DRIVER_IDS)
def test_evals_behind_prefix_counts_round_ahead_only(make, behind):
    """Only the round-ahead driver queues an eval behind the next
    round's prefix: every round but the last."""
    run, counters = make()
    run()
    assert counters.evals_behind_prefix == behind


def test_counters_count_a_padded_odd_cohort():
    """Three survivors of one capacity group train in a bucket of four:
    three cohort rows and one padding slot."""
    sim = FLSimulation(_cfg())
    g = max(sim.groups, key=lambda g: g.size)
    surv = np.zeros(sim.n, bool)
    surv[g.client_ids[:3]] = True
    counters = trace.RoundCounters()
    out = pipeline.train_groups(
        sim.params, sim.groups, sim._group_steps, surv, sim._round_keys(0),
        epochs=1, batch_size=sim.cfg.batch_size, lr=sim.cfg.lr,
        prox_mu=0.0, counters=counters)
    assert out[1].shape == (4,)
    assert (counters.cohort_rows, counters.cohort_pad_rows) == (3, 1)


def test_counters_follow_the_rounds():
    """The simulation's counters: one fence per round, survivors trained as
    cohort rows (the rows' ``n_aggregated``), no election re-run."""
    sim = FLSimulation(_cfg())
    rows = sim.run(ROUNDS)
    c = sim.counters
    assert c.rounds == ROUNDS and c.elect_reruns == 0
    assert c.cohort_rows == sum(r["n_aggregated"] for r in rows)
    assert 0 <= c.cohort_pad_rows <= c.cohort_rows


def test_counters_count_election_reruns(tmp_path):
    """A windowed election whose window is too small overflows every
    round: each round re-runs dense (an ``fl.elect_rerun`` span inside
    the fence) and counts, and the masks are the gather election's."""
    sim = FLSimulation(_cfg(), run=RunConfig(elect="windowed"))
    sim.stage_cfg = dataclasses.replace(sim.stage_cfg, elect_window=1)
    ref = FLSimulation(_cfg(), run=RunConfig(elect="gather"))
    with jax.profiler.trace(str(tmp_path)):
        rows = sim.run(2)
    assert rows == ref.run(2)
    assert (sim.counters.rounds, sim.counters.elect_reruns) == (2, 2)
    spans = _host_spans(str(tmp_path))
    fences = [(s, e) for s, e, n, a in spans if n == trace.FENCE]
    reruns = [(s, e) for s, e, n, a in spans if n == trace.ELECT_RERUN]
    assert len(reruns) == 2
    assert all(any(fs <= s and e <= fe for fs, fe in fences)
               for s, e in reruns)


def _op_names(compiled_text):
    return re.findall(r'op_name="([^"]*)"', compiled_text)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_prefix_stage_scopes_reach_the_compiled_hlo(fused):
    """Each stage of the compiled ``selection_prefix`` keeps its scope
    in the instructions' ``op_name``."""
    sim = FLSimulation(_cfg(), run=RunConfig(fused_probe=fused))
    text = pipeline.selection_prefix.lower(
        sim.statics, sim.params, jnp.int32(0), sim.key, sim.net_key,
        cfg=sim.stage_cfg).compile().as_text()
    ops = _op_names(text)
    for scope in trace.SCOPES:
        assert any(f"/{scope}/" in o for o in ops), scope


def test_fedavg_is_its_own_named_executable():
    """FedAvg lowers as ``jit_fedavg_round`` (its device-trace module)
    and averages as ``fedavg_masked`` does."""
    stack = {"w": jnp.arange(12.0).reshape(3, 4)}
    w = jnp.asarray([1.0, 3.0, 0.0])
    lowered = jax.jit(pipeline.fedavg_round).lower(stack, w)
    assert "jit_fedavg_round" in lowered.compile().as_text().splitlines()[0]
    got = pipeline.aggregate(None, ({"w": stack["w"] + 0.0}, w))
    np.testing.assert_array_equal(
        np.asarray(got["w"]), np.asarray((stack["w"][0] + 3 * stack["w"][1])
                                         / 4.0))


_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import dataclasses, json, re
import jax, jax.numpy as jnp
from repro.fl import pipeline, trace
from repro.fl.mobility import MobilityConfig
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig, FLSimulation
from repro.fl.runconfig import RunConfig
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import DEFAULT_RULES, logical_sharding

N = 10
cfg = FLSimConfig(
    scheme="dcs", n_rounds=2, local_epochs=1, samples_per_class=260,
    probe_samples=64,
    partition=PartitionConfig(n_clients=N, big_clients=3, big_quantity=120,
                              small_quantity=40, classes_per_client=9),
    mobility=MobilityConfig(n_vehicles=N))
out = {}
mesh = make_clients_mesh(4)
with mesh, logical_sharding(mesh, DEFAULT_RULES):
    sim = FLSimulation(cfg, run=RunConfig(elect="windowed"))
    text = pipeline._sharded_prefix_fn(sim.stage_cfg, mesh, False).lower(
        sim.statics, sim.params, jnp.int32(0), sim.key,
        sim.net_key).compile().as_text()
    ops = re.findall(r'op_name="([^"]*)"', text)
    out["scopes"] = {s: sum(f"/{s}/" in o for o in ops)
                     for s in trace.SCOPES}
    # the overflow@resume clamp: every windowed round overflows
    sim.stage_cfg = dataclasses.replace(sim.stage_cfg, elect_capacity=1)
    sim.run(2)
    out["counters"] = dataclasses.asdict(sim.counters)
print(json.dumps(out))
"""


def test_sharded_prefix_scopes_and_rerun_counter():
    """On 4 emulated CPU devices the sharded prefix body carries the
    same four stage scopes, and a clamped election capacity re-runs (and
    counts) every round."""
    env = {**os.environ, "PYTHONPATH": str(REPO / "src")}
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert all(out["scopes"][s] > 0 for s in trace.SCOPES), out["scopes"]
    assert out["counters"]["rounds"] == 2
    assert out["counters"]["elect_reruns"] == 2
