"""Required-work counts against hand counts, and their independence of
padding."""
import numpy as np
import pytest

from benchmarks.chip import metrics, work

# hand counts: conv1 28*28*25*32, conv2 14*14*25*32*64, fc1 3136*512,
# fc2 512*10
MACS = 627_200 + 10_035_200 + 1_605_632 + 5_120


def table3_valid():
    return [4500] * 12 + [45] * 18


def fleet1k_valid():
    # 9 classes x floor(q / 9): 256 -> 252, 24 -> 18
    return [252] * 12 + [18] * 1012


def test_cnn_counts():
    assert work.MACS_PER_ROW == MACS == 12_273_152
    assert work.FWD_FLOPS_PER_ROW == 2 * MACS
    assert work.PARAM_COUNT == 1_663_370


@pytest.mark.parametrize("valid, rows", [(table3_valid(), 12 * 256 + 18 * 45),
                                         (fleet1k_valid(), 12 * 252 + 1012 * 18)])
def test_probe_rows(valid, rows):
    assert work.probe_rows(valid, 256) == rows
    assert rows in (3_882, 21_240)
    w = work.probe_work(valid, 256)
    assert w["flops"] == rows * 2 * MACS
    assert w["bytes"] == rows * (28 * 28 * 4 + 4) + 1_663_370 * 4


def test_train_and_eval_flops():
    # one data-rich Table 3 vehicle: 4,500 samples x 30 epochs x 3 passes
    assert work.train_flops([4500], 30) == 4500 * 30 * 3 * 2 * MACS
    assert work.train_flops([4500], 30) == pytest.approx(9.94e12, rel=1e-3)
    assert work.eval_flops(9_900) == 9_900 * 2 * MACS


def test_padding_rows_never_count():
    """The counts read valid samples only: padding a vehicle's capacity
    (45 samples in a 60-row slot, a 128-row aligned probe) leaves them,
    and the shares read from them, unchanged."""
    valid = np.asarray(table3_valid())
    assert work.probe_work(valid, 256) == work.probe_work(list(valid), 256)
    ctx = {"window": {"window_s": 1.0, "busy_s": 0.5}, "kind": "TPU v5 lite",
           "chips": 1, "work": {"probe_flops": 1e12, "train_flops": 0,
                                "eval_flops": 2e12}}
    spec = {"kind": "mfu", "flops": ["probe_flops", "train_flops",
                                     "eval_flops"]}
    assert metrics.read(spec, ctx) == pytest.approx(100 * 3e12 / 197e12)


def test_roofline_bound():
    r = work.roofline_s(197e12, 819e9 / 2, "TPU v5 lite")
    assert r == {"s": pytest.approx(1.0), "bound": "compute"}
    assert work.roofline_s(1.0, 819e9, "TPU v5 lite")["bound"] == "memory"


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")
