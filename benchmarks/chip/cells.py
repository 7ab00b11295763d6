"""Find a cell's files by name and build the program's configs from them.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files live beside this module:

- ``configs/<name>.json``: every ``FLSimConfig``, ``PartitionConfig``,
  ``MobilityConfig``, ``NetworkConfig`` and ``RunConfig`` field that
  shapes the work, plus the constants the plain reference needs and the
  limits of the numbers ``correct`` compares;
- ``traffic/<name>.json``: how the driver is run (warm-up rounds,
  rounds checked) and its ``RunConfig`` overrides;
- ``metrics/<name>.json``: one per-layer metric's source and arithmetic.

Nothing here calls a program default: a later change to one cannot move
the yardstick.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Dict, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_FILE = "BENCHMARK.json"


def load_json(path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_bench(root: Path = ROOT) -> Dict:
    return load_json(Path(root) / BENCH_FILE)


def cell(bench: Dict, workload: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in {BENCH_FILE} (have "
                   f"{[w['name'] for w in bench['workloads']]})")


def config_entry(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in {BENCH_FILE}")


def resolve(workload: str, root: Path = ROOT) -> Dict:
    """Everything one run of ``workload`` reads: its ``BENCHMARK.json``
    entry, the configuration and traffic files, and the metric files of
    the metrics it reports."""
    root = Path(root)
    here = root / HERE.relative_to(ROOT)
    bench = load_bench(root)
    w = cell(bench, workload)
    conf = load_json(root / config_entry(bench, w["config"])["file"])
    traffic = load_json(here / "traffic" / f"{w['traffic']}.json")

    def reports(m: Dict) -> bool:
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    layer = [dict(m, spec=load_json(here / "metrics" / f"{m['name']}.json"))
             for m in bench["per_layer"] if reports(m)]
    return {"bench": bench, "cell": w, "config": conf, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def sim_config(conf: Dict):
    """The ``FLSimConfig`` of a configuration file.

    The deployment (data, partition, placement, speeds, slowdowns, the
    initial model and the channel realizations of every round) is the
    configuration's own ``deployment_seed``, so every run does the same
    work.  Any input the rounds read decides which vehicles are elected
    and meet Eq. 6, so it would change the work; a run's ``--seed``
    draws only the rounds the check compares."""
    from repro.fl.mobility import MobilityConfig
    from repro.fl.network import NetworkConfig
    from repro.fl.partition import PartitionConfig
    from repro.fl.rounds import FLSimConfig
    d = conf["deployment_seed"]
    sim = dict(conf["sim"])
    sim["slowdown_range"] = tuple(sim["slowdown_range"])
    return FLSimConfig(
        **sim, seed=d,
        partition=PartitionConfig(**conf["partition"], seed=d),
        mobility=MobilityConfig(**conf["mobility"], seed=d),
        network=NetworkConfig(**conf["network"], seed=d))


def run_config(conf: Dict, traffic: Dict):
    """The ``RunConfig``: the configuration's fields, then the traffic
    mix's overrides (the server and its staleness axis)."""
    from repro.fl.runconfig import RunConfig
    kw = dict(conf["run"])
    kw.update(traffic.get("run", {}))
    return RunConfig(**kw).resolved()



def out_dir(root: Path = ROOT, sub: Optional[str] = None) -> Path:
    """The benchmark's scratch directory inside the checkout."""
    p = Path(root) / ".bench"
    if sub:
        p = p / sub
    os.makedirs(p, exist_ok=True)
    return p
