"""The program's spans on a trace's time base (``chipbench/spans.py``) and
the four readers built on them, on a hand-made trace and hand-made span
records: the anchor, the idle inside ``study.execute``, the sums of walls
and counters, and ``None`` where there is nothing to read."""
import sys

import pytest

from chipbench import harness, spans
from chipbench import trace as trace_mod
from chipbench.trace import Trace
from repro.tracing import Record

DEV = "/device:TPU:0"
#: a realtime stamp, as the program records them
T0 = 1_792_000_000_000_000_000


def _study(first_id: int, t0: int, optimize_us: int, realize_us: int,
           syncs: int) -> list:
    """The records of one ``study.run`` root starting at ``t0``."""
    root = first_id
    spec = [  # (name, parent offset, start us, end us, counts)
        ("study.run", None, 0, 100, {"n_patients": 8}),
        ("study.optimize", 0, 1, 1 + optimize_us, {}),
        ("optimize.plan_capacities", 1, 2, 12, {"host_syncs": 5}),
        ("study.execute", 0, 22, 62, {}),
        ("execute.wait", 3, 23, 60, {"host_syncs": 1}),
        ("execute.stats", 3, 60, 61, {"host_syncs": syncs}),
        ("study.realize", 0, 63, 63 + realize_us, {}),
        ("realize.flow", 6, 64, 65, {"host_syncs": 3}),
    ]
    return [Record(root + i, None if p is None else root + p, root, name,
                   t0 + s * 1000, t0 + e * 1000, {}, dict(c))
            for i, (name, p, s, e, c) in enumerate(spec)]


def _records() -> list:
    warm = _study(1, T0, 900, 900, 100)            # set-up's study: ignored
    other = [Record(50, None, 50, "service.queued", T0, T0 + 5, {}, {})]
    one = _study(100, T0 + 10**9, 20, 30, 7)
    two = _study(200, T0 + 2 * 10**9, 30, 20, 9)
    return warm + other + one + two


def _trace(studies=((10_000, 115_000), (150_000, 260_000)), window=300_000):
    ops = {DEV: [("%fusion.1 = s32[] fusion()", 30_000, 50_000),
                 ("%fusion.2 = s32[] fusion()", 60_000, 65_000),
                 ("%while.3 = s32[] while()", 170_000, 212_500)]}
    bench = [("bench.window", 0, window)] + [("bench.study", s, e)
                                             for s, e in studies]
    return Trace.from_events(ops, bench, (0, window))


class _Run:
    def __init__(self, trace):
        self.trace = trace


def _read(name, trace):
    return harness._load_path("metrics", name).read(_Run(trace))


def test_anchor_moves_each_root_onto_its_study():
    got = spans.studies(_trace(), _records())
    assert [len(s) for s in got] == [8, 8]
    for (b0, _), study, first in zip(((10_000, 0), (150_000, 0)), got,
                                     (100, 200)):
        by = {r.name: r for r in study}
        assert {r.root_id for r in study} == {first}
        assert by["study.run"].start_ns == b0
        assert by["study.execute"].start_ns == b0 + 22_000
        assert by["study.execute"].end_ns == b0 + 62_000
        assert by["study.run"].end_ns == b0 + 100_000


def test_idle_inside_execute():
    # study one's execute is [32, 72] us: ops [30, 50] and [60, 65] cover
    # 23 us of it; study two's [172, 212] lies inside one op
    st = spans.studies(_trace(), _records())
    assert spans.idle_ms(_trace(), st[0], "study.execute") == \
        pytest.approx(17_000e-6)
    assert spans.idle_ms(_trace(), st[1], "study.execute") == 0.0


@pytest.mark.parametrize("metric,want", [
    ("study.plan_ms", (20 + 30) / 2 * 1e-3),
    ("study.execute_idle_ms", (17_000 + 0) / 2 * 1e-6),
    ("study.realize_ms", (30 + 20) / 2 * 1e-3),
    ("study.host_syncs", ((5 + 1 + 7 + 3) + (5 + 1 + 9 + 3)) / 2),
])
def test_readers_average_over_the_traced_studies(monkeypatch, metric, want):
    monkeypatch.setattr(spans, "program_records", _records)
    assert _read(metric, _trace()) == pytest.approx(want)


def _full(monkeypatch):
    monkeypatch.setattr(trace_mod, "BUFFER_EVENTS", 4096)
    return _trace()


@pytest.mark.parametrize("case", ["full", "no_whole_study", "no_root",
                                  "fewer_roots", "untraced"])
def test_nothing_to_read(monkeypatch, case):
    records = _records()
    trace = _trace()
    if case == "full":
        trace = _full(monkeypatch)
        assert trace.full
    elif case == "no_whole_study":
        trace = _trace(studies=((250_000, 350_000),))
    elif case == "no_root":
        records = [r for r in records if r.name != "study.run"]
    elif case == "fewer_roots":
        records = _study(1, T0, 20, 30, 7)
    else:
        trace = None
    monkeypatch.setattr(spans, "program_records", lambda: records)
    assert spans.studies(trace) is None
    for m in ("study.plan_ms", "study.execute_idle_ms", "study.realize_ms",
              "study.host_syncs"):
        assert _read(m, trace) is None


def test_program_without_the_recorder(monkeypatch):
    """A checkout whose program records no spans reads nothing, and does
    not raise."""
    import repro

    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing")
    assert spans.program_records() == []
    assert _read("study.plan_ms", _trace()) is None
