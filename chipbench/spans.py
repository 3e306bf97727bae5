"""The program's own spans, placed on a reduced trace's time base.

The program records its host-side phases in memory (``repro.tracing``),
stamped with the clock the profiler stamps its host events with; the trace
keeps only the benchmark's ``bench.*`` spans.  For the k whole
``bench.study`` spans of a trace, ``studies`` takes the last k ``study.run``
roots the program recorded and shifts every span of each root so that the
root starts where its ``bench.study`` span starts.  The error of that anchor
is the host time between the two openings (a few microseconds; measured by
``chipbench/tools/check_span_clock.py``).

A trace that filled the profiler's buffer, a window with no whole study, or
a program that recorded fewer roots (one without the recorder) gives
``None``, and every reader built on it returns ``None``.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np

STUDY_SPAN = "bench.study"
ROOT = "study.run"


def program_records() -> list:
    """The program's span records, oldest first; none from a program
    without the recorder."""
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.recorded()


def studies(trace, records: Optional[Sequence] = None
            ) -> Optional[List[list]]:
    """Per whole ``bench.study`` span of ``trace``, in order, the records of
    the ``study.run`` root it ran, with ``start_ns`` and ``end_ns`` moved to
    the trace's time base (floats, ns).  ``records`` defaults to the
    program's own."""
    if trace is None:
        return None
    benches = sorted(trace.whole_spans(STUDY_SPAN))
    if not benches:
        return None
    recs = program_records() if records is None else list(records)
    roots = [r for r in recs if r.name == ROOT and r.parent_id is None]
    if len(roots) < len(benches):
        return None
    out = []
    for (b0, _), root in zip(benches, roots[-len(benches):]):
        # integer differences first: the stamps are about 1.8e18 ns, past
        # what a float holds to the nanosecond
        out.append([r._replace(start_ns=b0 + (r.start_ns - root.start_ns),
                               end_ns=b0 + (r.end_ns - root.start_ns))
                    for r in recs if r.root_id == root.id])
    return out


def per_study(run, value: Callable[[list], float]) -> Optional[float]:
    """The mean over the traced studies of ``value(spans of one study)``."""
    st = studies(run.trace)
    if not st:
        return None
    return float(np.mean([value(s) for s in st]))


def wall_ms(spans: list, name: str) -> float:
    """Summed wall of the spans named ``name``, in ms."""
    return sum(r.wall_ns for r in spans if r.name == name) * 1e-6


def counted(spans: list, key: str) -> int:
    """Summed counter ``key`` over the spans."""
    return sum(r.counts.get(key, 0) for r in spans)


def idle_ms(trace, spans: list, name: str) -> float:
    """Device idle inside the spans named ``name``, in ms: each span's wall
    less the union of the device's ops inside it, averaged over the
    devices."""
    idle = [sum(r.wall_ns - trace.busy_ns(d, r.start_ns, r.end_ns)
                for r in spans if r.name == name) for d in trace.ops]
    return float(np.mean(idle)) * 1e-6 if idle else 0.0
