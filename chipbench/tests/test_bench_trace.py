"""The trace reduction: busy union, idle gaps, nesting and the readers, on
hand-made traces, on a hand-made ``.xplane.pb`` read by ``reduce``, and on
a small trace recorded on a TPU v5e (the first study of a traced
``study.batch`` window at 1,024 patients, recorded with
``chipbench/tools/record_trace.py``)."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import harness
from chipbench.trace import (Ops, Trace, instruction, is_pallas, reduce,
                             self_ns, union_ns)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "trace_small.json.gz")
#: what the reduction read from the fixture when it was recorded: 40 ms of
#: a study holding four predicate kernel calls and a run of sorts
KNOWN = {"shape": (0.04, 1141, 2), "busy_s": 0.039999351,
         "metrics": {"study.host_ms": 0.000649,
                     "plan_ops.sort_ms": 1.526695,
                     "plan_ops.loop_ms": 3.132567,
                     "kernels_roofline.study": 29.90448704734419,
                     "device.idle.study": 0.0016224999999980838}}


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.kernels = harness.kernel_models()
        self.peaks = harness.load_peaks("TPU v5 lite")


def _read(name, trace):
    return harness._load_path("metrics", name).read(_Run(trace))


def test_busy_union_and_gaps_by_hand():
    ops = {"/device:TPU:0": [("%a.1 = s32[] add()", 10, 20),
                             ("%b.2 = s32[] fusion(%sort.1)", 15, 30),
                             ("%sort.3 = s32[8] sort(%b.2)", 50, 60),
                             ("%c.4 = s32[] copy()", 95, 120)]}
    spans = [("bench.window", 0, 100), ("bench.study", 5, 45),
             ("bench.study", 45, 100)]
    t = Trace.from_events(ops, spans, (0, 100))
    # [10, 30] + [50, 60] + [95, 100] inside the window
    assert t.busy_ns("/device:TPU:0", 0, 100) == 35
    assert t.busy_s == pytest.approx(35e-9)
    assert _read("device.idle.study", t) == pytest.approx(65.0)
    # study 1: 40 ns of wall, 20 busy; study 2: 55 ns, 15 busy
    assert _read("study.host_ms", t) == pytest.approx((20 + 40) / 2 * 1e-6)
    assert _read("plan_ops.sort_ms", t) == pytest.approx(10 / 2 * 1e-6)
    gaps = t.idle_gaps(3)
    assert gaps[0] == ("bench.study", pytest.approx(35e-9))   # 60..95
    assert [g[1] for g in gaps] == pytest.approx([35e-9, 20e-9, 10e-9])
    assert _read("kernels_roofline.study", t) is None          # no kernel


def _union(intervals):
    """Length of a union of intervals, by a sweep over sorted endpoints."""
    ev = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in
                                                    intervals])
    depth, last, total = 0, None, 0.0
    for t, d in ev:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_recorded_v5e_trace():
    t = Trace.load(FIXTURE)
    dev = sorted(t.ops)
    assert dev == ["/device:TPU:0"]
    lo, hi = t.window
    evs = [(max(s, lo), min(e, hi)) for _, s, e in t.events(dev[0])
           if e > lo and s < hi]
    assert t.busy_ns(dev[0], lo, hi) == pytest.approx(_union(evs))
    assert (t.window_s, t.ops[dev[0]].name.size,
            len(t.spans)) == KNOWN["shape"]
    assert t.busy_s == pytest.approx(KNOWN["busy_s"], rel=1e-9)
    for name, want in KNOWN["metrics"].items():
        assert _read(name, t) == pytest.approx(want, rel=1e-9), name
    # the kernels there are the predicate's, named by their wrapper
    calls = [n for n, _, _ in t.events(dev[0]) if is_pallas(n)]
    assert calls and all(instruction(n).startswith("_predicate_bitset_jit")
                         for n in calls)


@pytest.mark.parametrize("events, union, own", [
    # disjoint
    ([(0, 10), (20, 25)], 15, [10, 5]),
    # a loop holding two body ops, then a third op
    ([(0, 100), (10, 20), (30, 60), (120, 130)], 110, [60, 10, 30, 10]),
    # two depths, the inner op starting with its parent
    ([(0, 50), (0, 40), (5, 15), (60, 70)], 60, [10, 30, 10, 10]),
    # overlapping, not nested, on one line
    ([(0, 10), (5, 20)], 20, [10, 15]),
])
def test_union_and_self_time(events, union, own):
    ops = Ops.build(np.zeros(len(events)), [s for s, _ in events],
                    [e for _, e in events])
    assert union_ns(ops) == union
    assert self_ns(ops).tolist() == own


def _xspace(path):
    """An ``.xplane.pb`` as the TPU profiler lays one out: a device plane
    whose ``XLA Ops`` line holds a loop and its body, and a host plane
    holding the benchmark's spans among other events.  Line timestamps are
    absolute; event offsets are in picoseconds."""
    from chipbench.xplane_pb2 import XSpace

    space = XSpace()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    names = {1: "%while.3 = (s32[]) while(s32[] %p), condition=%c",
             2: "%dynamic-slice.7 = s32[1]{0} dynamic-slice(s32[8]{0} %a)",
             3: "%sort.2 = s32[8]{0} sort(s32[8]{0} %b), "
                "backend_config={\"x\": 1}"}
    for i, n in names.items():
        dev.event_metadata[i].id = i
        dev.event_metadata[i].name = n
    dev.lines.add(id=1, name="Steps")
    ops = dev.lines.add(id=2, name="XLA Ops", timestamp_ns=1_000_000)
    for mid, off_ns, dur_ns in [(1, 100, 400), (2, 150, 50), (2, 300, 50),
                                (3, 600, 100)]:
        ops.events.add(metadata_id=mid, offset_ps=off_ns * 1000,
                       duration_ps=dur_ns * 1000)
    host = space.planes.add(id=2, name="/host:CPU")
    for i, n in {1: "bench.window", 2: "bench.study", 3: "PjitFunction"
                 }.items():
        host.event_metadata[i].id = i
        host.event_metadata[i].name = n
    line = host.lines.add(id=7, name="main", timestamp_ns=999_950)
    for mid, off_ns, dur_ns in [(1, 0, 900), (2, 100, 800), (3, 120, 5)]:
        line.events.add(metadata_id=mid, offset_ps=off_ns * 1000,
                        duration_ps=dur_ns * 1000)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_reduce_reads_an_xplane(tmp_path):
    _xspace(str(tmp_path / "plugins" / "profile" / "r" / "h.xplane.pb"))
    t = reduce(str(tmp_path), [SimpleNamespace(id=0)])
    # times are ns after the earliest line (the host's, 50 ns earlier)
    assert t.window == (0.0, 900.0)
    assert t.spans == [("bench.window", 0.0, 900.0),
                       ("bench.study", 100.0, 900.0)]
    assert [(instruction(n), s, e) for n, s, e in
            t.events("/device:TPU:0")] == [
        ("while.3", 150.0, 550.0), ("dynamic-slice.7", 200.0, 250.0),
        ("dynamic-slice.7", 350.0, 400.0), ("sort.2", 650.0, 750.0)]
    assert all("backend_config" not in n for n in t.names)
    assert t.busy_s == pytest.approx(500e-9)
    assert _read("plan_ops.loop_ms", t) == pytest.approx(400e-6)
    assert _read("plan_ops.sort_ms", t) == pytest.approx(100e-6)
    assert _read("study.host_ms", t) == pytest.approx(300e-6)
    ops = dict(t.breakdown()["device_ops"])
    assert ops == pytest.approx({"while": 300e-9, "dynamic-slice": 100e-9,
                                 "sort": 100e-9})
    # before the study, and after its last op
    assert t.idle_gaps(2) == [
        ("outside benchmark spans", pytest.approx(150e-9)),
        ("bench.study", pytest.approx(150e-9))]


def test_reduce_without_a_trace(tmp_path):
    with pytest.raises(RuntimeError, match="no trace"):
        reduce(str(tmp_path), [SimpleNamespace(id=0)])


def test_full_buffer_leaves_out_the_per_study_metrics(monkeypatch):
    from chipbench import trace

    ops = {"/device:TPU:0": [("%a.1 = s32[] add()", 10, 20),
                             ("%sort.3 = s32[8] sort(%b.2)", 50, 60)]}
    spans = [("bench.window", 0, 100), ("bench.study", 5, 95)]
    t = Trace.from_events(ops, spans, (0, 100))
    assert not t.full and _read("plan_ops.sort_ms", t) == pytest.approx(1e-5)
    # a buffer of 2 events, as full as the v5e's at 6 Mi
    monkeypatch.setattr(trace, "BUFFER_EVENTS", 4096 + 2)
    assert t.full and t.whole_spans("bench.study") == []
    for name in ("study.host_ms", "plan_ops.sort_ms", "plan_ops.loop_ms",
                 "device.idle.study"):
        assert _read(name, t) is None, name
