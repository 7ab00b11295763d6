"""Find a cell's files by name and build the program's configs from them.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files live beside this module:

- ``configs/<name>.json``: every ``FLSimConfig``, ``PartitionConfig``,
  ``MobilityConfig``, ``NetworkConfig`` and ``RunConfig`` field that
  shapes the work, plus the constants the plain reference needs and the
  limits of the numbers ``correct`` compares;
- ``traffic/<name>.json``: how the driver is run (warm-up rounds,
  rounds checked) and its ``RunConfig`` overrides;
- ``metrics/<name>.json``: one per-layer metric's source and arithmetic;
- ``families/<name>.py``: the model a configuration trains, named by its
  ``"family"`` key: the program's ``FLSimConfig``, the data the
  reference reads, the model's stages of the reference and its required
  work (``families/cnn.py`` lists what every family exposes).

Nothing here calls a program default: a later change to one cannot move
the yardstick.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
from pathlib import Path
from types import ModuleType
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
            "family": family(conf, root), "end_to_end": e2e,
            "per_layer": layer}


def family(conf: Dict, root: Path = ROOT) -> ModuleType:
    """The module of the model ``conf`` trains: ``families/<name>.py``
    beside this module in ``root``'s checkout, ``<name>`` its
    ``"family"`` key.  Loaded by path, so a checkout runs only its own,
    and once per path in a process."""
    if "family" not in conf:
        raise KeyError(f"configuration {conf.get('name')!r} has no "
                       f"\"family\" key naming families/<name>.py")
    rel = HERE.relative_to(ROOT) / "families" / f"{conf['family']}.py"
    path = (Path(root) / rel).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"configuration {conf.get('name')!r} names "
                                f"family {conf['family']!r}, but {rel} is "
                                f"not in {root}")
    name = "_bench_family_" + hashlib.sha1(str(path).encode()).hexdigest()
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        try:
            spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def sim_config(conf: Dict, root: Path = ROOT):
    """The program's ``FLSimConfig`` of a configuration file, as its
    family builds it."""
    return family(conf, root).sim_config(conf)


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
