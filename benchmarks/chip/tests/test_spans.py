"""The span, scope and counter readers on synthetic traces (no chip)."""
import pytest
from jax.profiler import ProfileData

from benchmarks.chip import metrics, spans, xplane


def _plane(pid, name, lines, names):
    """A text XPlane: ``lines`` of ``(name, timestamp_ns, events)``, each
    event ``(metadata_id, offset_ns, duration_ns)``."""
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in names.items())
    body = ""
    for lid, (lname, ts, events) in enumerate(lines, 1):
        evs = "".join(f"events {{ metadata_id: {m} offset_ps: {o * 1000} "
                      f"duration_ps: {d * 1000} }}\n" for m, o, d in events)
        body += (f'lines {{ id: {lid} name: "{lname}" timestamp_ns: {ts}\n'
                 f'{evs}}}\n')
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


def _op(instr, path):
    """An op event's name as a v5e trace gives it, the instruction's HLO
    text; here with its ``op_name`` when ``path`` is given."""
    meta = f', metadata={{op_name=\\"{path}\\"}}' if path else ""
    return f"%{instr} = f32[8]{{0}} fusion(%p){meta}"


def _profile(with_paths=True):
    """One device, window [1000, 2000]: ops busy [1100, 1350] and
    [1700, 1800], so idle [1000, 1100], [1350, 1700], [1800, 2000];
    the prefix executable [1100, 1350] holds a probe op [1100, 1300] and
    an elect op [1250, 1350]; FedAvg runs [1700, 1800].  Host spans:
    fl.fence [1300, 1500], fl.cohort [1500, 1750], two fl.round steps."""
    p = (lambda path: path) if with_paths else (lambda path: None)
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", 1000, [(1, 100, 200), (2, 250, 100), (3, 700, 100)]),
        ("XLA Modules", 1000, [(4, 100, 250), (5, 700, 100)]),
    ], {1: _op("fusion.1", p("jit(selection_prefix)/probe/dot_general")),
        2: _op("sort.3", p("jit(selection_prefix)/elect/sort")),
        3: _op("fusion.1", p("jit(fedavg_round)/dot_general")),
        4: "jit_selection_prefix(12)", 5: "jit_fedavg_round(13)"})
    host = _plane(2, "/host:CPU", [
        ("python3", 1000, [(1, 0, 1000), (2, 0, 500), (2, 500, 500),
                           (3, 300, 200), (4, 500, 250)]),
    ], {1: "bench.window", 2: "fl.round", 3: "fl.fence", 4: "fl.cohort"})
    return ProfileData.from_text_proto(dev + host)


MODULES = ("selection_prefix", "fedavg_round")


@pytest.fixture
def ctx():
    pd = _profile()
    return {"trace": xplane.from_profile(pd), "lo": 1000.0, "hi": 2000.0,
            "rounds": 2, "op_paths": spans.op_paths(pd, MODULES),
            "counters": {"rounds": 4, "elect_reruns": 1,
                         "cohort_rows": 6, "cohort_pad_rows": 2}}


def test_intersect_is_exact():
    assert spans.intersect_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.intersect_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.intersect_ns([], [(0, 1)]) == 0


def test_span_idle_is_the_exact_intersection(ctx):
    tr = ctx["trace"]
    # fence [1300, 1500] meets the gap [1350, 1700] over 150 ns
    assert spans.span_idle_ns(tr, ["fl.fence"], 1000, 2000) == 150
    # cohort [1500, 1750] meets it over [1500, 1700]
    assert spans.span_idle_ns(tr, ["fl.cohort"], 1000, 2000) == 200
    got = metrics.read({"kind": "span_idle_ms_per_round",
                        "spans": ["fl.fence"]}, ctx)
    assert got == pytest.approx(150e-6 / 2)


def test_gaps_outside_every_span_are_not_counted(ctx):
    tr = ctx["trace"]
    # the window's idle is 100 + 350 + 200 ns; the fl.* child spans
    # cover 350 of it, the gaps at either end lie outside them
    idle = sum(e - s for s, e in xplane.gaps(tr.ops["/device:TPU:0"],
                                             1000, 2000))
    assert idle == 650
    assert spans.span_idle_ns(tr, ["fl.fence", "fl.cohort"],
                              1000, 2000) == 350
    split = spans.idle_by_span(tr, 1000, 2000, rounds=1)
    assert split["outside fl.* child spans"] == pytest.approx(300e-6)
    assert split["fl.round"] == pytest.approx(650e-6)


def test_disjoint_spans_sum_to_at_most_the_idle(ctx):
    tr = ctx["trace"]
    parts = [spans.span_idle_ns(tr, [n], 1000, 2000)
             for n in ("fl.fence", "fl.cohort")]
    assert sum(parts) <= 650


def test_scope_from_the_op_name(ctx):
    probe = metrics.read({"kind": "scope_ms_per_round",
                          "modules": ["selection_prefix"],
                          "scope": "probe"}, ctx)
    elect = metrics.read({"kind": "scope_ms_per_round",
                          "modules": ["selection_prefix"],
                          "scope": "elect"}, ctx)
    # the elect op starts before the probe op ends and takes the overlap,
    # so the scopes sum to the executable's 250 ns
    assert probe == pytest.approx(150e-6 / 2)
    assert elect == pytest.approx(100e-6 / 2)
    # FedAvg's op is no probe op
    assert metrics.read({"kind": "scope_ms_per_round",
                         "modules": ["fedavg_round"], "scope": "probe"},
                        ctx) is None


def test_scope_from_an_hlo_text():
    pd = _profile(with_paths=False)
    assert spans.op_paths(pd, MODULES) == {"/device:TPU:0": []}
    text = ('HloModule jit_selection_prefix\n'
            '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(selection_prefix)/probe/dot_general"}\n'
            '  ROOT %sort.3 = f32[8]{0} sort(%fusion.1), '
            'metadata={op_name="jit(selection_prefix)/elect/sort" '
            'stack_frame_id=2}\n')
    hlo = spans.hlo_paths(text)
    assert hlo["sort.3"] == "jit(selection_prefix)/elect/sort"
    # only ops inside the named executables are mapped: FedAvg's
    # fusion.1 shares the prefix's instruction name, not its module
    paths = spans.op_paths(pd, ["selection_prefix"], hlo)["/device:TPU:0"]
    assert paths == [(1100.0, 1250.0,
                      "jit(selection_prefix)/probe/dot_general"),
                     (1250.0, 1350.0, "jit(selection_prefix)/elect/sort")]


def test_counter_share(ctx):
    assert metrics.read({"kind": "counter_share",
                         "counter": "elect_reruns", "of": ["rounds"]},
                        ctx) == 25.0
    assert metrics.read({"kind": "counter_share",
                         "counter": "cohort_pad_rows",
                         "of": ["cohort_rows", "cohort_pad_rows"]},
                        ctx) == 25.0


def test_readers_return_none_when_nothing_matches(ctx):
    empty = dict(ctx, counters={"backend_compile": 0}, op_paths={})
    share = {"kind": "counter_share", "counter": "elect_reruns",
             "of": ["rounds"]}
    assert metrics.read(share, empty) is None
    assert metrics.read(share, dict(ctx, counters={"rounds": 0,
                                                   "elect_reruns": 0})) \
        is None
    assert metrics.read({"kind": "span_idle_ms_per_round",
                         "spans": ["fl.read"]}, ctx) is None
    for c, scope in ((empty, "probe"), (ctx, "deadline")):
        assert metrics.read({"kind": "scope_ms_per_round",
                             "modules": ["selection_prefix"],
                             "scope": scope}, c) is None


def test_overlapping_ops_count_once():
    """A loop op with its body inside it, and an op of another scope
    that starts before the loop op ends: every instant is counted for
    one op, the latest started, so the scopes sum to the busy time."""
    ops = [(0, 100, "f/probe/while"), (10, 30, "f/probe/while/body/dot"),
           (90, 120, "f/elect/sort")]
    assert spans.exclusive(ops) == [
        (0, 10, "f/probe/while"), (10, 30, "f/probe/while/body/dot"),
        (30, 90, "f/probe/while"), (90, 120, "f/elect/sort")]
    assert spans.exclusive([(0, 10, "a"), (20, 30, "a")]) == \
        [(0, 10, "a"), (20, 30, "a")]
    assert spans.exclusive([]) == []


def test_top_ops_name_their_executable_and_scope(ctx):
    top = spans.top_ops(ctx["trace"], ctx["op_paths"], 1000, 2000)
    assert top[0][:3] == ["%fusion.1", "jit_selection_prefix",
                          "jit(selection_prefix)/probe/dot_general"]
    assert top[0][3] == pytest.approx(200e-9)
    assert [t[1] for t in top] == ["jit_selection_prefix",
                                   "jit_selection_prefix",
                                   "jit_fedavg_round"]
