"""Check the program's span stamps against the profiler's clock on the
chip, and measure the anchor error of ``chipbench/spans.py``.

    python chipbench/tools/check_span_clock.py <seed> [<seed> ...]

For each seed, the ``study.batch`` cell's set-up (data from the seed, one
warm study), then one study of its window under the profiler, as a
``--trace 1`` run traces it, keeping the ``.xplane.pb``.  Prints one JSON
line per seed (and writes them all to ``chiprun_out/span_clock.json``):

- ``clock``: every span the program recorded for the traced study against
  its ``TraceAnnotation`` on the host plane: the same names in the same
  order and nesting and, once one common offset (the session's start) is
  removed, starts and ends within ``LIMIT_US``;
- ``anchor_us``: the host time from the ``bench.study`` annotation's start
  to the ``study.run`` annotation's, the error of the anchor
  ``spans.studies`` takes; ``aligned_us``: the largest distance between a
  span as ``spans.studies`` places it on the reduced trace and its
  annotation there;
- ``metrics``: the per-layer metrics read from the program's spans, with
  ``study.host_ms`` beside them;
- ``phases``: per span name, its wall and the device idle inside it (ms);
- ``uncovered_idle_share``: the device idle inside ``study.run`` under none
  of its child spans, over the idle inside ``study.run``.

Needs a TPU.
"""
import gc
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

LIMIT_US = 50.0
METRICS = ("study.plan_ms", "study.execute_idle_ms", "study.realize_ms",
           "study.host_syncs", "study.host_ms")


def host_annotations(log_dir: str, names) -> list:
    """``(name, start_ns, end_ns, line)`` of the host-plane events named in
    ``names``, in the ``.xplane.pb``'s own time base, ordered by start and,
    at one start, longest first."""
    import glob

    from chipbench.xplane_pb2 import XSpace

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    space = XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host"):
            continue
        ids = {i for i, m in plane.event_metadata.items() if m.name in names}
        for line in plane.lines:
            for e in line.events:
                if e.metadata_id in ids:
                    s = line.timestamp_ns + e.offset_ps * 1e-3
                    out.append((plane.event_metadata[e.metadata_id].name, s,
                                s + e.duration_ps * 1e-3,
                                (plane.name, line.id)))
    return sorted(out, key=lambda a: (a[1], a[1] - a[2]))


def _nesting(items) -> list:
    """``(name, parent name)`` per ``(name, start, end)`` item, ordered by
    start, the parent being the innermost earlier item that holds it."""
    out, stack = [], []
    for name, s, e in items:
        while stack and stack[-1][2] < e:
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


def root_annotations(anns: list, root: str = "study.run") -> list:
    """The annotations inside the last ``root`` annotation, on its line."""
    top = [a for a in anns if a[0] == root][-1]
    return [a for a in anns if a[3] == top[3] and a[1] >= top[1]
            and a[2] <= top[2]]


def compare(records: list, anns: list) -> dict:
    """One study's span records (one root) against its annotations."""
    recs = sorted(records, key=lambda r: (r.start_ns, r.start_ns - r.end_ns))
    by_id = {r.id: r.name for r in recs}
    out = {"spans": len(recs), "annotations": len(anns),
           "names": [r.name for r in recs] == [a[0] for a in anns]}
    out["nesting"] = out["names"] and (
        [(r.name, by_id.get(r.parent_id)) for r in recs]
        == _nesting([(a[0], a[1], a[2]) for a in anns]))
    if not out["names"]:
        out["ok"] = False
        return out
    t0, a0 = recs[0].start_ns, anns[0][1]
    d_start = [(a[1] - a0) - (r.start_ns - t0) for r, a in zip(recs, anns)]
    d_end = [(a[2] - a0) - (r.end_ns - t0) for r, a in zip(recs, anns)]
    offset = sorted(d_start)[len(d_start) // 2]
    out["start_dev_us"] = max(abs(d - offset) for d in d_start) * 1e-3
    out["end_dev_us"] = max(abs(d - offset) for d in d_end) * 1e-3
    out["ok"] = bool(out["nesting"] and out["start_dev_us"] <= LIMIT_US
                     and out["end_dev_us"] <= LIMIT_US)
    return out


def study_report(trace, anns: list, records: list) -> dict:
    """Anchor, metrics, phases and uncovered idle of the last traced study
    (``trace``: the reduced trace; ``anns``: its host annotations)."""
    from chipbench import spans

    study = spans.studies(trace, records)[-1]
    bench = [a for a in anns if a[0] == spans.STUDY_SPAN][-1]
    run_ann = [a for a in anns if a[0] == spans.ROOT][-1]
    # reduced trace time = xplane time - shift
    shift = bench[1] - sorted(trace.whole_spans(spans.STUDY_SPAN))[-1][0]
    mine = root_annotations(anns)
    placed = sorted(study, key=lambda r: (r.start_ns,
                                          r.start_ns - r.end_ns))
    aligned = max(max(abs(r.start_ns - (a[1] - shift)),
                      abs(r.end_ns - (a[2] - shift)))
                  for r, a in zip(placed, mine))
    names = sorted({r.name for r in study})
    phases = {n: {"wall_ms": spans.wall_ms(study, n),
                  "idle_ms": spans.idle_ms(trace, study, n)}
              for n in names}
    root = [r for r in study if r.parent_id is None][0]
    children = {r.name for r in study if r.parent_id == root.id}
    idle_root = phases[spans.ROOT]["idle_ms"]
    uncovered = idle_root - sum(phases[n]["idle_ms"] for n in children)
    return {"anchor_us": (run_ann[1] - bench[1]) * 1e-3,
            "aligned_us": aligned * 1e-3,
            "phases": phases,
            "uncovered_idle_ms": uncovered,
            "uncovered_idle_share": uncovered / idle_root
            if idle_root > 0 else None}


def check_seed(cell: dict, seed: int, devices) -> dict:
    from chipbench import harness, traffic
    from chipbench import trace as trace_mod
    from repro import tracing

    mix = traffic.load(cell["traffic"])
    drv = harness._load_path("loops", mix["kind"]).Cell(
        harness.load_config(cell["config"]), mix, seed, devices)
    drv.setup(0.0)
    tdir = tempfile.mkdtemp(prefix="span_clock_")
    try:
        with trace_mod.Tracer(tdir) as tracer:
            drv.window(0.0)
        reduced = trace_mod.reduce(tdir, devices)
        records = tracing.recorded()
        root = [r for r in records if r.name == "study.run"][-1]
        mine = [r for r in records if r.root_id == root.id]
        names = {r.name for r in mine} | {"bench.study"}
        anns = host_annotations(tdir, names)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    out = {"seed": seed, "profiler_stop_s": tracer.stop_s,
           "full": reduced.full,
           "clock": compare(mine, root_annotations(anns))}
    run = harness.Run(cell, drv, reduced, harness.kernel_models(),
                      harness.load_peaks(devices[0].device_kind))
    out["metrics"] = {m: harness._load_path("metrics", m).read(run)
                      for m in METRICS}
    if not reduced.full:
        out.update(study_report(reduced, anns, records))
    drv.last = drv.last_answer = drv.star = drv.study = None
    gc.collect()
    return out


def main() -> int:
    import jax
    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("check_span_clock: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.find_cell(harness.load_benchmark(ROOT), "study.batch")
    harness.enable_compile_cache(ROOT)
    devices = jax.devices()[:1]
    rows = []
    for seed in (int(s) for s in sys.argv[1:]):
        rows.append(check_seed(cell, seed, devices))
        print(json.dumps(rows[-1]), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "span_clock.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r["clock"]["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
