"""The trace reducer on a synthetic XSpace (no chip needed)."""
import pytest

from benchmarks.chip import xplane


def _plane(pid, name, lines, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in names.items())
    body = ""
    for lid, (lname, ts, events) in enumerate(lines, 1):
        evs = "".join(f"events {{ metadata_id: {m} offset_ps: {o * 1000} "
                      f"duration_ps: {d * 1000} }}\n" for m, o, d in events)
        body += (f'lines {{ id: {lid} name: "{lname}" timestamp_ns: {ts}\n'
                 f'{evs}}}\n')
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def synthetic():
    """One device with three ops and two executables inside a 1,000 ns
    window [1000, 2000], and a host line with the bench spans."""
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", 1000, [(1, 100, 200), (2, 250, 100), (1, 700, 100)]),
        ("XLA Modules", 1000, [(3, 100, 250), (4, 700, 100)]),
    ], {1: "fusion.1", 2: "convolution.3",
        3: "jit_selection_prefix(12)", 4: "jit__count_correct(13)"})
    host = _plane(2, "/host:CPU", [
        ("python3", 1000, [(1, 0, 1000), (2, 0, 500), (2, 500, 500),
                           (3, 400, 200)]),
    ], {1: "bench.window", 2: "bench.round", 3: "PjitFunction(_count)"})
    from jax.profiler import ProfileData
    return xplane.from_profile(ProfileData.from_text_proto(dev + host))


def test_intervals():
    evs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c")]
    assert xplane.union(evs) == [(0, 15), (20, 30)]
    assert xplane.busy_ns(evs, 0, 40) == 25
    assert xplane.busy_ns(evs, 12, 25) == 8
    assert xplane.gaps(evs, 0, 40) == [(15, 20), (30, 40)]
    assert xplane.clip(evs, 8, 22) == [(8, 10, "a"), (8, 15, "b"),
                                       (20, 22, "c")]


def test_reduce_window_on_synthetic_trace():
    tr = synthetic()
    assert tr.devices == ["/device:TPU:0"]
    lo, hi = xplane.span(tr, "bench.window")
    assert (lo, hi) == (1000, 2000)
    red = xplane.reduce_window(tr, lo, hi)
    assert red["window_s"] == pytest.approx(1000e-9)
    # ops cover [1100,1300] u [1250,1350] u [1700,1800] = 250 + 100 ns
    assert red["busy_s"] == pytest.approx(350e-9)
    assert red["modules_s"] == pytest.approx(
        {"jit_selection_prefix": 250e-9, "jit__count_correct": 100e-9})
    assert red["device_ops"][0][0] == "fusion.1"
    assert red["device_ops"][0][1] == pytest.approx(300e-9)
    # the longest gap, [1350, 1700], sits in the first round span and
    # the dispatch span covering its midpoint (1525)
    label, secs = red["idle_gaps"][0]
    assert secs == pytest.approx(350e-9)
    assert label == "bench.round / PjitFunction(_count)"


def test_matching_module_time():
    tr = synthetic()
    mods = tr.modules["/device:TPU:0"]
    assert xplane.matching_ns(mods, ["selection_prefix"], 1000, 2000) == 250
    assert xplane.matching_ns(mods, ["_local_train_batch"], 1000, 2000) \
        is None


def test_no_device_plane_is_an_error():
    from jax.profiler import ProfileData
    host = _plane(2, "/host:CPU", [("python3", 0, [(1, 0, 10)])],
                  {1: "bench.window"})
    tr = xplane.from_profile(ProfileData.from_text_proto(host))
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce_window(tr, 0, 10)
