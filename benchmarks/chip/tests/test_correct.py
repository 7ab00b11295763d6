"""``correct`` at a size a test run holds (the 6-vehicle ``tiny``
configuration, on the CPU): the program passes, the control fails, and
the whole run with the timed path broken underneath comes out false for
each fault a one-chip FL cell can have.  (No exchange between chips
exists on one chip, so that fault has no cell here.)"""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import control, harness
from benchmarks.chip.tests import tiny

SEED = 2**31 + 11


def run(seconds=1.0):
    return harness.run_cell("tiny.sync", SEED, seconds, False,
                            require_tpu=False, resolved=tiny.resolved())


def failing(result):
    return sorted(k for k, v in result["compared"].items()
                  if not (v["value"] <= v["limit"]))


@pytest.fixture(scope="module")
def window():
    res = tiny.resolved()
    got = harness.collect(harness.measure(res, SEED, 1.0, False))
    return res["config"], got


def test_program_is_correct_and_the_control_is_not(window):
    conf, got = window
    prog = harness.check_rounds(got["world"], conf, got["outs"],
                                conf["limits"])
    assert prog["correct"], prog["compared"]
    ctl = control.readings(got["world"], conf, got["outs"],
                           control.control(got["world"], conf))
    assert not ctl["correct"]
    assert {"lf_gap", "eval_diff", "param_gap"} <= set(
        failing(ctl)), ctl["compared"]


@pytest.mark.parametrize("fault,fails", [("half_batch", "fit_gap"),
                                         ("unchanged", "param_gap")])
def test_planted_faults_read_over_their_limits(window, fault, fails):
    """``control.py``'s planted faults, in the program's place, fail the
    number that is theirs to catch."""
    conf, got = window
    v = control.readings(got["world"], conf, got["outs"],
                         control.FAULTS[fault](got["world"], conf))
    assert not v["correct"] and fails in failing(v), v["compared"]


@pytest.fixture
def fresh():
    """Each fault is traced anew: no compiled program outlives a test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_state_returned_unchanged(monkeypatch, fresh):
    from repro.fl import pipeline
    monkeypatch.setattr(pipeline, "aggregate", lambda params, trained: params)
    r = run()
    assert not r["correct"] and "param_gap" in failing(r)
    assert r["compared"]["param_gap"]["value"] == pytest.approx(1.0)


def test_half_the_probe_batch_left_out(monkeypatch, fresh):
    from repro.fl import client
    orig = client.dataset_loss_packed

    def half(params, images, labels, seg, counts, n_clients, batch=512):
        keep = jnp.arange(seg.shape[0]) % 2 == 0
        kept = jnp.where(keep, seg, n_clients)
        n = jax.ops.segment_sum(keep.astype(jnp.int32), seg,
                                num_segments=n_clients + 1)[:n_clients]
        return orig(params, images, labels, kept, n, n_clients=n_clients,
                    batch=batch)

    monkeypatch.setattr(client, "dataset_loss_packed", half)
    r = run()
    assert not r["correct"] and "lf_gap" in failing(r)


def test_half_the_training_batch_left_out(monkeypatch, fresh):
    from repro.fl import pipeline
    orig = pipeline.local_train_batch_donated

    def half(params, images, labels, n_valid, keys, **kw):
        return orig(params, images, labels, n_valid // 2, keys, **kw)

    monkeypatch.setattr(pipeline, "local_train_batch_donated", half)
    r = run()
    assert not r["correct"] and "param_gap" in failing(r)


def test_selection_altered_where_made(monkeypatch, fresh):
    from repro.fl import schemes
    orig = schemes.dcs_select

    def flipped(pos, evals, **kw):
        m = orig(pos, evals, **kw)
        return m.at[0].set(1 - m[0])

    monkeypatch.setattr(schemes, "dcs_select", flipped)
    r = run()
    assert not r["correct"] and "elect_diff" in failing(r)


def test_accuracy_altered_where_read(monkeypatch, fresh):
    from repro.fl import rounds
    orig = rounds.evaluate_accuracy_async

    def off(params, images, labels, batch=1024):
        count, n = orig(params, images, labels, batch)
        return count + 2, n

    monkeypatch.setattr(rounds, "evaluate_accuracy_async", off)
    r = run()
    assert not r["correct"] and "acc_outside" in failing(r)
