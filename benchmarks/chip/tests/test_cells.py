"""A new cell is data only, and BENCHMARK.json keeps to its contract."""
import json
import re
import shutil

import pytest

from benchmarks.chip import cells, check

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_new_cell_is_data_only(tmp_path):
    """Add a configuration, a traffic mix and a cell to a copy of the
    benchmark: the harness resolves and builds them without code."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.HERE, root / "benchmarks" / "chip")
    bench = cells.load_bench()
    chip = root / "benchmarks" / "chip"
    conf = json.loads((chip / "configs" / "table3.json").read_text())
    conf["name"] = "table3_ccs"
    conf["sim"]["scheme"] = "ccs-fuzzy"
    (chip / "configs" / "table3_ccs.json").write_text(json.dumps(conf))
    traffic = json.loads((chip / "traffic" / "sync.json").read_text())
    traffic["name"] = "sync_serial"
    traffic["run"]["overlap_rounds"] = False
    (chip / "traffic" / "sync_serial.json").write_text(json.dumps(traffic))
    bench["configs"].append({"name": "table3_ccs", "source": "x",
                             "file": "benchmarks/chip/configs/table3_ccs.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "table3_ccs.serial",
                               "config": "table3_ccs",
                               "traffic": "sync_serial", "chips": 1,
                               "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    got = cells.resolve("table3_ccs.serial", root)
    assert got["config"]["sim"]["scheme"] == "ccs-fuzzy"
    cfg = cells.sim_config(got["config"])
    run = cells.run_config(got["config"], got["traffic"])
    assert cfg.scheme == "ccs-fuzzy"
    assert (cfg.seed == cfg.partition.seed == cfg.mobility.seed
            == cfg.network.seed == conf["deployment_seed"])
    assert run.overlap_rounds is False and run.server == "sync"
    assert {m["name"] for m in got["per_layer"]} >= {"device_idle_share",
                                                     "round_mfu"}


def test_benchmark_json_contract():
    bench = cells.load_bench()
    assert set(bench) == TOP
    assert 1 <= bench["run_seconds"] <= 51
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for w in bench["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in bench["configs"]:
        names += c["reduced"]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(bench["paths"][0] + "/")
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (cells.HERE / "metrics" / f"{m['name']}.json").exists()


@pytest.mark.parametrize("w", cells.load_bench()["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    got = cells.resolve(w["name"])
    cfg = cells.sim_config(got["config"])
    assert cfg.partition.n_clients == got["config"]["partition"]["n_clients"]
    assert set(got["config"]["limits"]) == set(check.NUMBERS)
