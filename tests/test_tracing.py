"""The span recorder (``repro.tracing``): ids, counters, the ring's bound,
the span tree of one ``Study.run``, and its stamps against the profiler's
own annotations of the same spans (``.xplane.pb``, CPU backend)."""
import os
import sys
import threading

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from repro import tracing  # noqa: E402


def _mine(names):
    return [r for r in tracing.recorded() if r.name in names]


def test_parent_and_root_ids():
    with tracing.span("t.root", job=7) as root:
        with tracing.span("t.child") as child:
            with tracing.span("t.grandchild"):
                pass
        with tracing.span("t.sibling"):
            pass

        def work():
            with tracing.span("t.worker", parent=child):
                with tracing.span("t.worker_child"):
                    pass

        th = threading.Thread(target=work)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
    queued = tracing.begin("t.queued", ticket=3)
    with tracing.span("t.stage", parent=queued):
        pass
    queued.end()
    queued.end()                     # a second end records nothing
    recs = {r.name: r for r in _mine({
        "t.root", "t.child", "t.grandchild", "t.sibling", "t.worker",
        "t.worker_child", "t.queued", "t.stage"})[-8:]}
    assert len(recs) == 8
    assert recs["t.root"].parent_id is None
    assert recs["t.root"].root_id == root.id == recs["t.root"].id
    assert recs["t.root"].attrs == {"job": 7}
    assert recs["t.child"].parent_id == root.id
    assert recs["t.grandchild"].parent_id == child.id
    assert recs["t.sibling"].parent_id == root.id
    # a span on a worker thread takes the explicit parent, and its own
    # children take it from that thread's stack
    assert recs["t.worker"].parent_id == child.id
    assert recs["t.worker_child"].parent_id == recs["t.worker"].id
    assert {recs[n].root_id for n in ("t.child", "t.grandchild", "t.sibling",
                                      "t.worker", "t.worker_child")} \
        == {root.id}
    # begin() opens a root unless given a parent; a later stage of it can
    # start after it ended
    assert recs["t.queued"].parent_id is None
    assert recs["t.queued"].attrs == {"ticket": 3}
    assert recs["t.stage"].root_id == recs["t.queued"].id
    assert sum(r.id == queued.id for r in tracing.recorded()) == 1
    for r in recs.values():
        assert 0 <= r.wall_ns == r.end_ns - r.start_ns
    assert recs["t.root"].start_ns <= recs["t.child"].start_ns \
        <= recs["t.grandchild"].start_ns <= recs["t.grandchild"].end_ns \
        <= recs["t.child"].end_ns <= recs["t.root"].end_ns


def test_counters_and_raising_blocks():
    with pytest.raises(ValueError):
        with tracing.span("t.counted") as s:
            s.count("host_syncs")
            s.count("host_syncs", 4)
            s.count("bytes_to_host", 1024)
            s.count("compiled", False)
            raise ValueError("the span is still recorded")
    rec = _mine({"t.counted"})[-1]
    assert rec.counts == {"host_syncs": 5, "bytes_to_host": 1024,
                          "compiled": 0}
    assert s.seconds == rec.wall_ns * 1e-9
    # the stack unwound: the next span is a root again
    with tracing.span("t.after") as after:
        pass
    assert after.parent_id is None


def test_ring_is_bounded():
    n = tracing.RING_SIZE + 5
    for i in range(n):
        with tracing.span("t.ring", i=i):
            pass
    recs = tracing.recorded()
    assert len(recs) == tracing.RING_SIZE
    assert [r.attrs["i"] for r in recs] == list(range(5, n))


# -- one Study.run of the paper's study at 128 patients, traced -------------
@pytest.fixture(scope="module")
def traced_study(tmp_path_factory):
    from chipbench import harness, traffic
    from chipbench.study_cell import StudyCell

    cfg = dict(harness.load_config("snds_paper_study_1chip"), n_patients=128)
    cell = StudyCell(cfg, traffic.load("study_closed"), 3_000_000_017)
    cell.setup(0.0)                  # data and one warm study
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        res = cell.study.run(dict(cell.star))
    root = [r for r in tracing.recorded() if r.name == "study.run"][-1]
    spans = [r for r in tracing.recorded() if r.root_id == root.id]
    return res, spans, log_dir


def test_study_span_tree(traced_study):
    res, spans, _ = traced_study
    assert len(spans) <= 30
    by_id = {r.id: r for r in spans}
    tree = sorted((by_id[r.parent_id].name if r.parent_id else "", r.name)
                  for r in spans)
    assert tree == sorted([
        ("", "study.run"),
        ("study.run", "study.optimize"),
        ("study.optimize", "optimize.plan_capacities"),
        ("study.run", "study.execute"),
        ("study.execute", "execute.dispatch"),
        ("study.execute", "execute.wait"),
        ("study.execute", "execute.stats"),
        ("study.execute", "execute.record"),
        ("study.run", "study.realize"),
        ("study.realize", "realize.cohorts"),
        ("study.realize", "realize.flow"),
        ("study.realize", "realize.featurize"),
        ("study.realize", "realize.featurize")])
    one = {r.name: r for r in spans}
    assert one["study.run"].counts == {"n_patients": 128}
    plan = one["optimize.plan_capacities"].counts
    assert plan["joins"] > 0 and plan["host_syncs"] > 0
    assert plan["bytes_to_host"] >= 4 * plan["host_syncs"]
    assert one["execute.dispatch"].counts == {"compiled": 0}   # warm
    assert one["execute.wait"].counts == {"host_syncs": 1}
    n_stats = sum(1 for d in res.flatten_stats.values() for k in d
                  if k != "stage")
    assert one["execute.stats"].counts == {"host_syncs": n_stats}
    assert one["realize.flow"].counts == {"host_syncs": len(res.flow.steps)}
    feats = {r.attrs["name"]: r for r in spans
             if r.name == "realize.featurize"}
    assert {k: r.attrs["kind"] for k, r in feats.items()} == {
        "X": "dense", "tokens": "tokens"}
    for name, r in feats.items():
        assert r.counts == {
            "host_syncs": len(res.feature_checks[name]) + 1}


def test_study_spans_match_profiler_annotations(traced_study):
    from chipbench.tools import check_span_clock as clock

    _, spans, log_dir = traced_study
    anns = clock.host_annotations(log_dir, {r.name for r in spans})
    got = clock.compare(spans, clock.root_annotations(anns))
    assert got["names"] and got["nesting"], got
    assert got["start_dev_us"] <= clock.LIMIT_US, got
    assert got["end_dev_us"] <= clock.LIMIT_US, got
    assert got["ok"]
