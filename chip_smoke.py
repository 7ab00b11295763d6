"""Bring-up smoke check of the FL round on a TPU chip.

    python chip_smoke.py               # one chip: phases (a)-(f) below
    python chip_smoke.py --four-chips  # the client-mesh path on four chips

One chip, in one process:

(a) device check — exits non-zero unless jax sees a TPU (never falls back
    to the CPU);
(b) the Table 3 profile (paper CNN, 30 vehicles: 12 x 4500 samples and 18
    small, 30 local epochs, 20 s deadline) for 3 rounds of ``dcs`` and of
    ``ccs-fuzzy`` through ``repro.launch.fl_sim.main`` — accuracies
    finite, some round selects clients, the global params move iff a
    round aggregated.  Under Table 3's Eq. 6 timing a data-rich vehicle
    needs 400-1,500 s of local training against the 20 s deadline and
    most uploads alone take over 20 s, so these rounds usually aggregate
    nothing; the same CNN therefore also runs 3 ``dcs`` rounds of the
    CPU-budget profile (1 local epoch, 60 s deadline), which must train
    a cohort and move the params;
(c) the same round 0 again with the Pallas kernels (the jit caches are
    cleared first: the impl is read while tracing) — the lowered prefix
    must hold a ``tpu_custom_call`` and select the same clients as (b);
(d) round 0's selection prefix on the host CPU, reported beside the
    chip's: the largest relative gap of the evaluations and whether the
    masks agree (the chip's default matmul precision is not the CPU's,
    so this is reported, not asserted);
(e) each phase's compile seconds and steady seconds, on earlier lines —
    smoke numbers, not benchmark results;
(f) the last line: ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the multi-chip path and its reference: the
N=1024 Table-3-shaped fleet (12 x 256 samples, 1012 x 24) for two rounds
on a ``clients=4`` mesh with the windowed election, against a
``clients=1`` single-device run with the gather election.  Masks must be
bit-identical and params equal to 1e-5; the devices the sharded round
used and its election overflow count are printed.

Any failed phase raises, so the script exits non-zero without a last
line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parent
SCHEMES = ("dcs", "ccs-fuzzy")
TABLE3 = ("--paper-profile",)


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- (a) ---------------------------------------------------------------------

def device_check(platform: str = "tpu", count: int = 1) -> Dict:
    """The device block of the last line; raises ``SmokeFailure`` unless
    jax's first device is on ``platform`` and there are ``count``."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == platform,
          f"jax found no {platform} device (platform {dev['platform']!r}); "
          f"this check runs on the chip")
    check(dev["count"] >= count,
          f"needs {count} {platform} devices, jax sees {dev['count']}")
    return dev


# -- (e) ---------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


@contextlib.contextmanager
def compile_clock():
    """Sum jax's trace + lower + compile durations inside the block into
    ``clock["s"]``."""
    import jax
    clock = {"s": 0.0}

    def listen(event: str, secs: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            clock["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield clock
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@contextlib.contextmanager
def kernel_impl(impl: str):
    """Run the block with ``REPRO_KERNEL_IMPL=impl``.  The impl is read
    while tracing and no jit cache is keyed on it, so the caches are
    cleared on the way in and on the way out."""
    import jax
    old = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = impl
    jax.clear_caches()
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_KERNEL_IMPL", None)
        else:
            os.environ["REPRO_KERNEL_IMPL"] = old
        jax.clear_caches()


def differing(a, b) -> List[int]:
    """The clients whose selection masks ``a`` and ``b`` disagree on."""
    import numpy as np
    return np.nonzero(np.asarray(a) != np.asarray(b))[0].tolist()


def timed(fn):
    """``(fn(), seconds)``, with the result on the host."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# -- (b) ---------------------------------------------------------------------

def run_rounds(scheme: str, work: Path, rounds: int,
               profile: Sequence[str] = TABLE3, seed: int = 0,
               must_train: bool = False) -> Dict:
    """``rounds`` rounds of ``scheme`` through the ``fl_sim`` entry point,
    with per-round checkpoints, which give each round's selection mask,
    params and end time.  The global params must move iff some round
    aggregated an update (an empty round is an exact no-op broadcast);
    ``must_train`` requires that one did."""
    import jax
    import numpy as np
    from repro.configs.mnist_cnn import CONFIG as CNN_CFG
    from repro.launch import fl_sim
    from repro.models.cnn import init_cnn
    from repro.train.checkpoint import RoundCheckpointer, load_state

    work.mkdir(parents=True, exist_ok=True)
    out, ckpt = work / f"{scheme}.json", work / f"{scheme}.ckpt"
    argv = [*profile, "--scheme", scheme, "--rounds", str(rounds),
            "--seed", str(seed), "--out", str(out),
            "--checkpoint-dir", str(ckpt)]
    with compile_clock() as clock:
        t0 = time.perf_counter()
        rc = fl_sim.main(argv)
        wall = time.perf_counter() - t0
    check(rc == 0, f"fl_sim {' '.join(argv)} exited {rc}")
    rows = json.loads(out.read_text())[scheme]
    check(len(rows) == rounds, f"{scheme}: {len(rows)} rows, want {rounds}")
    snaps = RoundCheckpointer(str(ckpt / scheme))
    masks, ends, params = [], [], None
    for r in range(rounds):
        path = snaps.path_for(r)
        state, _ = load_state(path)
        masks.append(np.asarray(state["last_mask"]))
        ends.append(os.path.getmtime(os.path.join(path, "manifest.json")))
        params = state["params"]
    init = jax.device_get(init_cnn(jax.random.PRNGKey(seed), CNN_CFG))
    moved = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(init)))
    accs = [float(r["accuracy"]) for r in rows]
    n_sel = [int(r["n_selected"]) for r in rows]
    n_agg = [int(r["n_aggregated"]) for r in rows]
    log(f"(b) {scheme} {' '.join(profile)}: accuracy {accs} n_selected "
        f"{n_sel} n_aggregated {n_agg} params moved {moved:.3e}")
    check(all(math.isfinite(a) for a in accs),
          f"{scheme}: non-finite accuracy {accs}")
    check(any(n > 0 for n in n_sel), f"{scheme}: no round selected a client")
    check((moved > 0.0) == any(n_agg),
          f"{scheme}: params moved {moved:.3e} with {n_agg} aggregated")
    check(any(n_agg) or not must_train,
          f"{scheme}: no selected vehicle met the Eq. 6 deadline, so no "
          f"cohort trained")
    # round r ends when its checkpoint is committed, so these seconds
    # include writing one params snapshot
    steady = [b - a for a, b in zip(ends, ends[1:])]
    log(f"(e) {scheme}: wall {wall:.1f} s, compile {clock['s']:.1f} s, "
        f"steady round s incl. checkpoint write "
        f"{[round(s, 3) for s in steady]}")
    return {"masks": masks, "rows": rows}


# -- (c) ---------------------------------------------------------------------

def compare_impls(cfg, ref_mask, impl: str = "pallas",
                  want_kernel: bool = True) -> Dict:
    """Round 0 of ``cfg`` on the default (jnp) impl, then again on
    ``impl``: the masks must equal ``ref_mask`` (phase (b)'s round 0) and
    each other; with ``want_kernel`` the lowered prefix must hold a
    compiled Pallas call."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.fl import pipeline
    from repro.fl.rounds import FLSimulation

    sim = FLSimulation(cfg)
    base = jax.device_get(sim.selection_state(0))
    _, base_s = timed(lambda: sim.selection_state(0))
    check(not differing(base["mask"], ref_mask),
          "round 0 re-run selects other clients than the fl_sim run")
    with kernel_impl(impl):
        if want_kernel:
            lowered = pipeline.selection_prefix.lower(
                sim.statics, sim.params, jnp.int32(0), sim.key, sim.net_key,
                cfg=sim.stage_cfg)
            check("tpu_custom_call" in lowered.as_text(),
                  f"the {impl} prefix holds no tpu_custom_call: the kernels "
                  f"do not compile for the chip")
        with compile_clock() as clock:
            got = jax.device_get(sim.selection_state(0))
        _, got_s = timed(lambda: sim.selection_state(0))
        diff = differing(got["mask"], base["mask"])
        gap = float(np.max(np.abs(np.asarray(got["evals"])
                                  - np.asarray(base["evals"]))))
        log(f"(c) {impl} vs jnp round 0: masks identical {not diff}, "
            f"largest evaluation gap {gap:.3e}")
        check(not diff, f"{impl} masks differ from jnp at clients {diff}")
        row, round_s = timed(lambda: sim.run_round(0))
        check(math.isfinite(row["accuracy"]),
              f"{impl} round 0: non-finite accuracy")
    log(f"(e) prefix steady s: jnp {base_s:.4f}, {impl} {got_s:.4f}; "
        f"{impl} prefix compile {clock['s']:.1f} s; {impl} round 0 "
        f"{round_s:.1f} s, accuracy {row['accuracy']:.4f}")
    return {"state": base}


# -- (d) ---------------------------------------------------------------------

def compare_cpu(cfg, chip_state) -> Dict:
    """Round 0's prefix on the host CPU beside the chip's ``chip_state``
    (reported, not asserted)."""
    import jax
    import numpy as np
    from repro.fl.rounds import FLSimulation

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        sim = FLSimulation(cfg)
        with compile_clock() as clock:
            host = jax.device_get(sim.selection_state(0))
        on = {d for x in jax.tree.leaves(sim.selection_state(0))
              for d in x.sharding.device_set}
        _, cpu_s = timed(lambda: sim.selection_state(0))
    check(on == {cpu}, f"the cpu reference ran on {sorted(map(str, on))}")
    e_chip = np.asarray(chip_state["evals"], np.float64)
    e_cpu = np.asarray(host["evals"], np.float64)
    rel = float(np.max(np.abs(e_chip - e_cpu)
                       / np.maximum(np.abs(e_cpu), 1e-6)))
    differ = differing(chip_state["mask"], host["mask"])
    log(f"(d) chip vs cpu round 0: largest relative evaluation gap "
        f"{rel:.3e}; masks agree {not differ}"
        + (f"; differing clients {differ}" if differ else ""))
    log(f"(e) cpu prefix: compile {clock['s']:.1f} s, steady {cpu_s:.4f} s")
    return {"rel_gap": rel, "differ": differ}


def one_chip(work: Path, rounds: int = 3, profile: Sequence[str] = TABLE3,
             train_profile: Sequence[str] = (), impl: str = "pallas",
             want_kernel: bool = True) -> None:
    """Phases (b)-(e) for the profile ``profile`` of ``fl_sim``, plus a
    ``dcs`` run of ``train_profile`` that must train a cohort."""
    from repro.launch.fl_sim import sim_config
    runs = {s: run_rounds(s, work / "profile", rounds, profile)
            for s in SCHEMES}
    run_rounds("dcs", work / "train", rounds, train_profile,
               must_train=True)
    cfg = sim_config("dcs", paper_profile="--paper-profile" in profile,
                     rounds=rounds)
    got = compare_impls(cfg, runs["dcs"]["masks"][0], impl=impl,
                        want_kernel=want_kernel)
    compare_cpu(cfg, got["state"])


# -- --four-chips ------------------------------------------------------------

def fleet1k_config(n: int = 1024, big: int = 12, big_q: int = 256,
                   small_q: int = 24, local_epochs: int = 30):
    """The Table-3-shaped N=1024 fleet: Table 3 training (30 epochs,
    batch 20, 20 s deadline) over 12 data-rich and 1012 data-poor
    vehicles on a 2 km road."""
    from repro.fl.mobility import MobilityConfig
    from repro.fl.partition import PartitionConfig
    from repro.fl.rounds import FLSimConfig
    demand = big * big_q + (n - big) * small_q
    return FLSimConfig(
        scheme="dcs", local_epochs=local_epochs, deadline_s=20.0,
        samples_per_class=-(-demand * 5 // 40),   # 1.25x the per-class need
        partition=PartitionConfig(n_clients=n, big_clients=big,
                                  big_quantity=big_q, small_quantity=small_q),
        mobility=MobilityConfig(n_vehicles=n, road_length_m=2000.0))


def params_gap(p, q):
    """``(largest absolute gap, largest gap in ulps)`` of two params trees,
    each leaf's ulps taken at that leaf's largest magnitude."""
    import jax
    import numpy as np
    pairs = [(np.asarray(a), np.asarray(b)) for a, b in
             zip(jax.tree.leaves(jax.device_get(p)),
                 jax.tree.leaves(jax.device_get(q)))]
    return (max(float(np.max(np.abs(a - b))) for a, b in pairs),
            max(float(np.max(np.abs(a - b)) / np.spacing(np.max(np.abs(a))))
                for a, b in pairs))


def four_chips(rounds: int = 2, shards: int = 4, cfg=None) -> None:
    """The client-mesh path (windowed election, sharded prefix, psum'd
    trainer) against the single-device gather path, round by round.

    The sharded sim carries its own params over the rounds.  From round 1
    on a second sharded sim also runs each round from the reference's
    params, which compares one sharded round with one plain round: the
    psum'd FedAvg adds in another order than the single-device sum, and a
    later round of local SGD can amplify those ulps.  Masks must be
    identical in every run; params must agree to 1e-5 in round 0 and in
    each single-round comparison; the carried gap is printed."""
    import jax
    import numpy as np
    from repro.fl.rounds import FLSimulation
    from repro.fl.runconfig import RunConfig
    from repro.launch.mesh import client_mesh_context

    cfg = cfg or fleet1k_config()
    one, many = "clients=1", f"clients={shards}"
    with client_mesh_context(one):
        ref = FLSimulation(cfg, run=RunConfig(elect="gather"))
    with client_mesh_context(many):
        sh = FLSimulation(cfg, run=RunConfig(elect="windowed"))
        step = FLSimulation(cfg, run=RunConfig(elect="windowed"))
    check(sh.n_shards == shards, f"sharded sim has {sh.n_shards} shards")
    overflow, used = 0, set()
    for r in range(rounds):
        start = ref.params
        with client_mesh_context(one):
            a, ref_s = timed(lambda: ref.selection_state(r))
            a = jax.device_get(a)
            ra = ref.finish_round(r, a)
        runs = {"carried": sh} if r == 0 else {"carried": sh, "step": step}
        for tag, sim in runs.items():
            with client_mesh_context(many):
                if sim is step:
                    sim.params = start
                b, sh_s = timed(lambda: sim.selection_state(r))
                used |= {d.id for x in jax.tree.leaves(b)
                         for d in x.sharding.device_set}
                b = jax.device_get(b)
                flagged = int(np.max(b["elect_overflow"]))
                b = sim.resolve_elect_overflow(r, b)
                rb = sim.finish_round(r, b)
                used |= {d.id for x in jax.tree.leaves(sim.params)
                         for d in x.sharding.device_set}
            overflow += flagged
            diff = differing(a["mask"], b["mask"])
            gap, ulps = params_gap(ref.params, sim.params)
            log(f"round {r} {tag}: masks identical {not diff}, selected "
                f"{int(a['n_selected'])}, aggregated {rb['n_aggregated']}, "
                f"elect_overflow {flagged}; "
                f"params max gap {gap:.3e} ({ulps:.0f} ulps); accuracy "
                f"{ra['accuracy']:.4f} / {rb['accuracy']:.4f}; prefix s "
                f"(compile + run) gather {ref_s:.1f}, windowed {sh_s:.1f}")
            check(not diff, f"round {r} {tag}: windowed masks differ at "
                  f"clients {diff}")
            if r == 0 or sim is step:
                check(gap <= 1e-5, f"round {r} {tag}: params differ by "
                      f"{gap:.3e}")
    log(f"devices used by the sharded rounds: {sorted(used)}; "
        f"elect_overflow rounds {overflow}")
    check(len(used) == shards, f"sharded rounds ran on devices "
          f"{sorted(used)}, want {shards} distinct")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip client-mesh path and its "
                         "single-device reference")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        dev = device_check(count=4 if args.four_chips else 1)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 2
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            one_chip(Path(tmp))
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
