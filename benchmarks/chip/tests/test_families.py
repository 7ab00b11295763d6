"""The model a configuration trains is a family: a new one is files only,
an unknown one is an error, and the CNN's work counts are pinned."""
import json
import shutil
import types

import numpy as np
import pytest

from benchmarks.chip import cells, harness
from benchmarks.chip import reference as ref

FAKE = '''"""A family that returns fixed numbers and records its calls."""
import jax
import jax.numpy as jnp
import numpy as np

CALLS = []
WORK = {"probe_flops": 11, "probe_bytes": 12, "train_flops": 13,
        "eval_flops": 14}


def sim_config(conf):
    CALLS.append("sim_config")
    return ("fake sim config", conf["name"])


def world_data(sim):
    return {"rows": sim.n}


def n_test(sim):
    return 100


def probe_loss(world, conf, p, dtype=jnp.float32):
    CALLS.append("probe_loss")
    return jnp.full((world.n,), 0.5, jnp.float32)


def local_train(world, conf, p, i, rnd, dtype=jnp.float32):
    CALLS.append("local_train")
    return jax.tree.map(lambda a: (a + float(i + 1)).astype(dtype), p)


def count_correct(world, p, dtype=jnp.float32, margin=0.0):
    CALLS.append("count_correct")
    return 70, 70


def fit_loss(world, ids, p):
    CALLS.append("fit_loss")
    return 1.0


def trained_leaves(p):
    CALLS.append("trained_leaves")
    return [np.asarray(p["w"], np.float64)]


def work(conf, world, rounds, trained_ids):
    CALLS.append(("work", rounds, list(trained_ids)))
    return dict(WORK)
'''


def world(conf, data, family, n_valid, seed=0):
    """A fleet of ``len(n_valid)`` vehicles spread over the road."""
    n = len(n_valid)
    return ref.World(
        n_valid=np.asarray(n_valid, np.int64),
        slowdown=np.linspace(1.0, 2.0, n),
        x0=np.linspace(0.0, conf["mobility"]["road_length_m"], n,
                       endpoint=False),
        speeds=np.full(n, 25.0), jitter_phase=np.zeros(n),
        seed=seed, net_seed=seed, data=data, family=family)


def test_new_family_is_files_only(tmp_path):
    """A copy of the benchmark gains ``families/fake.py``, a configuration
    naming it and a cell: resolve, the check and the work counts all
    route to the new family, and no existing file is edited."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(cells.HERE, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "families" / "fake.py").write_text(FAKE)
    conf = json.loads((chip / "tests" / "tiny.json").read_text())
    conf.update(name="fake", family="fake")
    (chip / "configs" / "fake.json").write_text(json.dumps(conf))
    bench = cells.load_bench()
    bench["configs"].append({"name": "fake", "source": "x", "reduced": [],
                             "file": "benchmarks/chip/configs/fake.json",
                             "why": "x"})
    bench["workloads"].append({"name": "fake.sync", "config": "fake",
                               "traffic": "sync", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    got = cells.resolve("fake.sync", root)
    fake = got["family"]
    assert fake.__file__ == str((chip / "families" / "fake.py").resolve())
    assert cells.sim_config(got["config"], root) == ("fake sim config",
                                                     "fake")
    assert cells.family(conf, root) is fake

    w = world(conf, fake.world_data(types.SimpleNamespace(n=6)), fake,
              [45, 45, 18, 18, 18, 18])
    p_in = {"w": np.zeros(3, np.float32)}
    outs = {}
    for rnd in (0, 1):
        o = ref.run_round(w, conf, p_in, rnd)
        outs[rnd] = dict(o, p_in=p_in, replay=0)
    assert all(o["survivors"].any() for o in outs.values())
    fake.CALLS.clear()
    v = harness.check_rounds(w, conf, outs, conf["limits"])
    assert v["correct"], v["compared"]
    assert {"probe_loss", "local_train", "count_correct", "fit_loss",
            "trained_leaves"} <= set(fake.CALLS)
    outs[1] = dict(outs[1], count=73)
    v = harness.check_rounds(w, conf, outs, conf["limits"])
    assert v["compared"]["acc_outside"]["value"] == 3 and not v["correct"]

    fake.CALLS.clear()
    masks = [np.asarray(outs[r]["mask"]) for r in (0, 1)]
    clock = types.SimpleNamespace(times=[1.0, 2.0], masks=masks)
    assert harness.window_work(conf, w, clock) == fake.WORK
    trained = [i for r in (0, 1) for i in np.nonzero(outs[r]["survivors"])[0]]
    assert fake.CALLS == [("work", 2, trained)]

    after = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert set(after) - set(before) == {chip / "families" / "fake.py",
                                        chip / "configs" / "fake.json"}
    assert all(after[p] == b for p, b in before.items())


@pytest.mark.parametrize("conf, error, names", [
    ({"name": "x", "family": "nosuch"}, FileNotFoundError,
     r"families/nosuch\.py"),
    ({"name": "x"}, KeyError, r"families/<name>\.py"),
], ids=["unknown", "missing"])
def test_unknown_family_is_an_error(conf, error, names):
    with pytest.raises(error, match=names):
        cells.family(conf)


# the parent tree's integers: window_work of six rounds of the tiny
# cell's fleet (6 vehicles, 60 test images) under these masks, and the
# probe of the two benchmarked fleets
TINY_MASKS = [[1, 1, 1, 1, 1, 1], [1, 1, 1, 1, 1, 1], [1, 1, 0, 1, 0, 1],
              [1, 1, 1, 1, 1, 0], [0, 1, 1, 1, 1, 1], [1, 1, 0, 1, 1, 1]]
TINY_WORK = {"probe_flops": 23_859_007_488, "probe_bytes": 42_972_960,
             "train_flops": 62_961_269_760, "eval_flops": 8_836_669_440}
PROBE_WORK = {"table3": ([4500] * 12 + [45] * 18,
                         {"flops": 95_288_752_128, "bytes": 18_842_960}),
              "fleet1k": ([252] * 12 + [18] * 1012,
                          {"flops": 521_363_496_960, "bytes": 73_347_080})}


def test_cnn_work_counts_are_pinned():
    conf = json.loads((cells.HERE / "tests" / "tiny.json").read_text())
    cnn = cells.family(conf)
    data = cnn.Data(images=[], labels=[], test_images=np.zeros((60, 1)),
                    test_labels=np.zeros(60, np.int32))
    w = world(conf, data, cnn, [45, 45, 18, 18, 18, 18])
    clock = types.SimpleNamespace(times=[0.0] * 6,
                                  masks=[np.asarray(m) for m in TINY_MASKS])
    got = harness.window_work(conf, w, clock)
    assert got == TINY_WORK
    assert all(type(v) is int for v in got.values())
    for valid, want in PROBE_WORK.values():
        assert cnn.probe_work(valid, 256) == want
