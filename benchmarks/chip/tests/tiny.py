"""A tiny cell for CPU tests: the harness's whole run without a chip."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip import cells

HERE = Path(__file__).resolve().parent


def resolved(workload: str = "tiny.sync") -> dict:
    conf = json.loads((HERE / "tiny.json").read_text())
    traffic = cells.load_json(cells.HERE / "traffic" / "sync.json")
    bench = cells.load_bench()
    e2e = [m for m in bench["end_to_end"]]
    layer = [dict(m, spec=cells.load_json(cells.HERE / "metrics" /
                                          f"{m['name']}.json"))
             for m in bench["per_layer"]]
    return {"bench": bench, "cell": {"name": workload, "config": "tiny",
                                     "traffic": "sync", "chips": 1},
            "config": conf, "traffic": traffic,
            "family": cells.family(conf), "end_to_end": e2e,
            "per_layer": layer}
