"""The paper's experiment driver: federated simulation over the IoV model.

Usage:
  PYTHONPATH=src python -m repro.launch.fl_sim --scheme dcs --rounds 10
  PYTHONPATH=src python -m repro.launch.fl_sim --scheme all --fast
  PYTHONPATH=src python -m repro.launch.fl_sim --mesh clients=8 --rounds 5
  PYTHONPATH=src python -m repro.launch.fl_sim --server event \\
      --churn-rate 0.3 --staleness weighted --staleness-lambda 1.0

Execution knobs (engine / fused probe / round overlap / mesh / the
event-driven server's churn, staleness and cadence axis) live on the
shared ``RunConfig`` (``fl/runconfig.py``) — the same flags drive
``launch/sweep.py``, and library callers pass the identical object to
``FLSimulation(cfg, run=...)``.

``--mesh clients=K`` partitions the in-round client axis over K devices:
the selection prefix runs shard_map'd (``selection_prefix_sharded``) and
the grouped trainer splits every cohort across the mesh with a psum'd
FedAvg.  On CPU the K devices are emulated host devices — the launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=K`` before the
jax backend initializes (heavy imports are deferred into ``main`` for
exactly this reason); if the backend is already live, it raises with the
relaunch recipe instead of quietly running single-device.
"""
from __future__ import annotations

import argparse
import json
import time

SCHEMES = ("dcs", "ccs-fuzzy", "random")


def fast_config(scheme: str, **kw):
    """CPU-budget profile: same structure, smaller local datasets."""
    from repro.fl.partition import PartitionConfig
    from repro.fl.rounds import FLSimConfig
    part = PartitionConfig(big_quantity=kw.pop("big_quantity", 300),
                           small_quantity=45,
                           classes_per_client=kw.pop("classes_per_client", 9))
    return FLSimConfig(scheme=scheme, partition=part,
                       samples_per_class=kw.pop("samples_per_class", 600),
                       local_epochs=kw.pop("local_epochs", 1),
                       n_rounds=kw.pop("n_rounds", 10), **kw)


def paper_config(scheme: str, **kw):
    """Table 3 profile (expensive on CPU)."""
    from repro.fl.rounds import FLSimConfig
    return FLSimConfig(scheme=scheme, local_epochs=30, n_rounds=50,
                       deadline_s=20.0, **kw)


def sim_config(scheme: str, *, paper_profile: bool, rounds: int,
               classes_per_client: int = 9, seed: int = 0,
               distribution: str = "uniform"):
    """The ``FLSimConfig`` one ``fl_sim`` launch simulates for
    ``scheme``: the Table 3 profile or the CPU-budget one, on the
    freeway mobility field of ``seed``."""
    from repro.fl.mobility import MobilityConfig
    if paper_profile:
        cfg = paper_config(scheme, seed=seed)
    else:
        cfg = fast_config(scheme, n_rounds=rounds,
                          classes_per_client=classes_per_client, seed=seed)
    cfg.mobility = MobilityConfig(distribution=distribution, seed=seed)
    return cfg


def main(argv=None) -> int:
    # argparse only below — jax must not initialize before the mesh
    # context can force emulated host devices
    from repro.fl.runconfig import add_run_arguments
    from repro.launch.cache import add_cache_arguments, resolve_cache_dir
    from repro.launch.multihost import (add_multihost_arguments,
                                        multihost_from_args, should_spawn,
                                        spawn_multihost)

    ap = argparse.ArgumentParser()
    ap.add_argument("--scheme", choices=SCHEMES + ("all",), default="dcs")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--fast", action="store_true", default=True)
    ap.add_argument("--paper-profile", action="store_true")
    ap.add_argument("--classes-per-client", type=int, default=9)
    ap.add_argument("--distribution", choices=("uniform", "extreme"),
                    default="uniform")
    add_run_arguments(ap)        # mesh / fused probe / overlap / server /
    #                              churn / staleness / cadence (RunConfig)
    add_multihost_arguments(ap)  # --multihost P + hidden child flags
    add_cache_arguments(ap)      # --jit-cache-dir
    ap.add_argument("--out", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if should_spawn(args):
        # parent of a --multihost P launch: re-exec ourselves P times
        # with the coordinator flags appended and wait
        return spawn_multihost("repro.launch.fl_sim",
                               list(argv) if argv is not None
                               else __import__("sys").argv[1:],
                               args.multihost)

    # --mesh may force emulated host devices, which only works before the
    # jax backend initializes — so the mesh context comes first and the
    # simulator imports stay inside main
    from repro.launch.mesh import client_mesh_context
    with client_mesh_context(args.mesh,
                             multihost=multihost_from_args(args)) as mesh:
        import jax
        from repro.fl.rounds import FLSimulation
        from repro.fl.runconfig import RunConfig
        from repro.launch.cache import enable_jit_cache
        is_lead = jax.process_index() == 0
        enable_jit_cache(resolve_cache_dir(args.jit_cache_dir))
        if mesh is not None and is_lead:
            print(f"[fl_sim] client mesh: {dict(mesh.shape)} over "
                  f"{mesh.devices.size} devices"
                  + (f" / {jax.process_count()} processes"
                     if jax.process_count() > 1 else ""), flush=True)
        run = RunConfig.from_args(args)
        if run.server == "event" and is_lead:
            print(f"[fl_sim] event-driven server: churn={run.churn_rate} "
                  f"staleness={run.staleness} lam={run.staleness_lambda} "
                  f"cadence={run.agg_cadence_s or 'round period'}",
                  flush=True)

        schemes = SCHEMES if args.scheme == "all" else (args.scheme,)
        results = {}
        for scheme in schemes:
            cfg = sim_config(scheme, paper_profile=args.paper_profile,
                             rounds=args.rounds,
                             classes_per_client=args.classes_per_client,
                             seed=args.seed, distribution=args.distribution)
            srun = run
            if run.checkpoint_dir:
                # one snapshot directory per scheme, so --scheme all
                # runs never overwrite each other's round state
                import dataclasses
                import os
                srun = dataclasses.replace(
                    run, checkpoint_dir=os.path.join(run.checkpoint_dir,
                                                     scheme))
            sim = FLSimulation(cfg, run=srun)
            t0 = time.time()
            hist = sim.run(args.rounds)
            dt = time.time() - t0
            accs = [h["accuracy"] for h in hist]
            nsel = sum(h["n_selected"] for h in hist) / len(hist)
            if is_lead:
                print(f"[fl_sim] {scheme}: final acc {accs[-1]:.3f} "
                      f"(best {max(accs):.3f}), avg selected {nsel:.2f}, "
                      f"{dt:.0f}s; {sim.counters}", flush=True)
            results[scheme] = hist
    if args.out and is_lead:     # one writer in a multi-process launch
        from repro.ioutil import write_atomic_json
        write_atomic_json(args.out, results, indent=1)
        print(f"[fl_sim] wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
