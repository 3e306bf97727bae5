"""Profiler trace of the measured window, and its reduction to intervals.

``Tracer`` records the window with JAX's profiler (Python function tracing
off); the benchmark's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) land on the host plane of the same trace.
``reduce`` parses the ``.xplane.pb`` (``chipbench/xplane_pb2.py``, the
profiler's own message definitions) into a ``Trace``: per device, one
numpy array each of the op events' names, starts and ends, the host spans,
and the window.  A study's sequential scans put millions of op events in a
trace, so every reduction is a numpy pass over those arrays.  The per-layer
metric readers compute from a ``Trace`` only, so a recorded trace
(``Trace.load``) gives the same numbers as the live one.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: the device line holding one event per executed HLO op or kernel
OPS_LINE = "XLA Ops"
#: op events the v5e profiler keeps per device and trace: once its buffer
#: holds 6 Mi events it drops the rest, and the time they ran reads as idle
#: (a study at 8,192 patients kept 6,291,309 of about 24 M)
BUFFER_EVENTS = 6 * 2 ** 20


class Tracer:
    """``with Tracer(dir): ...`` traces the block, which is the window;
    ``stop_s`` is how long the profiler took to stop and write its trace."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.stop_s = 0.0

    def __enter__(self):
        import jax
        from jax.profiler import TraceAnnotation

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import time

        import jax

        self._span.__exit__(*exc)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0
        return False


class Ops(NamedTuple):
    """One device's op events: ``name`` indexes ``Trace.names``; ``start``
    and ``end`` are in ns.  Sorted by start and, at one start, longest
    first, so an op comes before the ops it holds (a ``while`` before its
    body's)."""

    name: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def build(cls, name, start, end) -> "Ops":
        name = np.asarray(name, np.int32)
        start = np.asarray(start, np.float64)
        end = np.asarray(end, np.float64)
        order = np.lexsort((start - end, start))
        return cls(name[order], start[order], end[order])

    def overlapping(self, lo: float, hi: float) -> "Ops":
        """The events overlapping [lo, hi], whole."""
        keep = (self.end > lo) & (self.start < hi)
        return Ops(self.name[keep], self.start[keep], self.end[keep])

    def clipped(self, lo: float, hi: float) -> "Ops":
        """The events overlapping [lo, hi], cut to it (order is kept)."""
        o = self.overlapping(lo, hi)
        return Ops(o.name, np.maximum(o.start, lo), np.minimum(o.end, hi))


def _reach(ops: Ops) -> np.ndarray:
    """Per event, the latest end of the events before it (-inf first)."""
    out = np.empty_like(ops.end)
    if out.size:
        out[0] = -np.inf
        np.maximum.accumulate(ops.end[:-1], out=out[1:])
    return out


def union_ns(ops: Ops) -> float:
    """Length of the union of the events' intervals."""
    return float(np.maximum(ops.end - np.maximum(ops.start, _reach(ops)),
                            0.0).sum())


def self_ns(ops: Ops) -> np.ndarray:
    """Per event, its length less that of the events directly inside it,
    so that nested ops (a loop's body inside its ``while``) are counted
    once.  Peels the nesting one depth at a time."""
    own = ops.end - ops.start
    cur = np.arange(own.size)
    outer = None
    while cur.size:
        sub = Ops(ops.name[cur], ops.start[cur], ops.end[cur])
        top = sub.end > _reach(sub)
        level = cur[top]
        if outer is not None and level.size:
            parent = np.searchsorted(ops.start[outer], ops.start[level],
                                     side="right") - 1
            own[outer] -= np.bincount(
                parent, weights=ops.end[level] - ops.start[level],
                minlength=outer.size)
        outer, cur = level, cur[~top]
    return own


class Trace:
    """Device op events and host spans of one traced window.

    ``names``: the ops' HLO texts as the TPU profiler gives them
    (``%fusion.12 = s32[...] fusion(...), ...``, less any
    ``backend_config``); ``ops``: per device, an ``Ops``;
    ``spans``: ``(name, start_ns, end_ns)`` of the ``bench.*`` spans;
    ``window``: ``(start_ns, end_ns)`` of the traced window."""

    def __init__(self, names: List[str], ops: Dict[str, Ops], spans: list,
                 window: Tuple[float, float]):
        self.names = names
        self.ops = ops
        self.spans = spans
        self.window = window
        self.families = [op_family(n) for n in names]

    @classmethod
    def from_events(cls, ops: Dict[str, list], spans: list,
                    window: Tuple[float, float]) -> "Trace":
        """From ``(name, start_ns, end_ns)`` tuples per device."""
        names: Dict[str, int] = {}
        arrays = {}
        for dev, evs in ops.items():
            ids = [names.setdefault(n, len(names)) for n, _, _ in evs]
            arrays[dev] = Ops.build(ids, [e[1] for e in evs],
                                    [e[2] for e in evs])
        return cls(list(names), arrays, [tuple(s) for s in spans],
                   tuple(window))

    def events(self, device: str) -> List[tuple]:
        o = self.ops[device]
        return [(self.names[i], s, e) for i, s, e in
                zip(o.name.tolist(), o.start.tolist(), o.end.tolist())]

    # -- persistence (a small recorded trace checks the reduction) ---------
    def dump(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": {d: self.events(d) for d in self.ops},
                       "spans": self.spans, "window": self.window}, f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls.from_events(d["ops"], d["spans"], d["window"])

    def cut(self, lo: float, hi: float) -> "Trace":
        """[lo, hi] as a trace of its own, ``lo..hi`` its window."""
        spans = [(n, max(a, lo), min(b, hi)) for n, a, b in self.spans
                 if b > lo and a < hi and n != WINDOW_SPAN]
        return Trace(self.names,
                     {d: o.clipped(lo, hi) for d, o in self.ops.items()},
                     spans + [(WINDOW_SPAN, lo, hi)], (lo, hi))

    # -- intervals -----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans_named(self, name: str) -> List[Tuple[float, float]]:
        return [(s, e) for n, s, e in self.spans if n == name]

    @property
    def full(self) -> bool:
        """Whether the profiler's buffer filled on some device, so that ops
        of the window are missing from the trace."""
        return any(o.name.size >= BUFFER_EVENTS - 4096
                   for o in self.ops.values())

    def whole_spans(self, name: str) -> List[Tuple[float, float]]:
        """The spans of that name inside the window, each with all its ops
        in the trace: none when the profiler's buffer filled."""
        lo, hi = self.window
        return [] if self.full else [(s, e) for s, e in
                                     self.spans_named(name)
                                     if s >= lo and e <= hi]

    def family_mask(self, ops: Ops, family: str) -> np.ndarray:
        ids = [i for i, f in enumerate(self.families) if f == family]
        return np.isin(ops.name, ids)

    def busy_ns(self, device: str, lo: float, hi: float,
                family: Optional[str] = None) -> float:
        """Length of the union of the device's op intervals (of one op
        family, if given) inside [lo, hi]."""
        ops = self.ops[device].clipped(lo, hi)
        if family is not None:
            keep = self.family_mask(ops, family)
            ops = Ops(ops.name[keep], ops.start[keep], ops.end[keep])
        return union_ns(ops)

    def family_ns(self, device: str, lo: float, hi: float,
                  family: str) -> float:
        """Summed length of one op family's events inside [lo, hi]."""
        ops = self.ops[device].clipped(lo, hi)
        keep = self.family_mask(ops, family)
        return float((ops.end[keep] - ops.start[keep]).sum())

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the traced devices."""
        lo, hi = self.window
        return float(np.mean([self.busy_ns(d, lo, hi) for d in self.ops])
                     * 1e-9) if self.ops else 0.0

    # -- breakdown for the ledger --------------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The op families that took most device time in the window, each
        op counted less the ops it holds, summed over devices; and the
        longest idle gaps."""
        lo, hi = self.window
        fam_ids = {f: i for i, f in enumerate(sorted(set(self.families)))}
        of_name = np.array([fam_ids[f] for f in self.families], np.int64)
        total = np.zeros(len(fam_ids))
        for ops in self.ops.values():
            ops = ops.clipped(lo, hi)
            total += np.bincount(of_name[ops.name], weights=self_ns(ops),
                                 minlength=len(fam_ids))
        order = np.argsort(-total, kind="stable")[:top]
        fams = sorted(fam_ids, key=fam_ids.get)
        return {"device_ops": [[fams[i], float(total[i]) * 1e-9]
                               for i in order if total[i] > 0],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps between device ops inside the window, each named
        by the innermost benchmark span the host was in when it opened."""
        lo, hi = self.window
        inner = [sp for sp in self.spans if sp[0] != WINDOW_SPAN]
        starts, ends = [], []
        for ops in self.ops.values():
            ops = ops.clipped(lo, hi)
            reach = np.maximum(_reach(ops), lo)
            gap = ops.start > reach
            starts.append(reach[gap])
            ends.append(ops.start[gap])
            last = max(lo, float(ops.end.max())) if ops.end.size else lo
            if hi > last:
                starts.append(np.array([last]))
                ends.append(np.array([hi]))
        if not starts:
            return []
        s, e = np.concatenate(starts), np.concatenate(ends)
        out = []
        for i in np.argsort(s - e, kind="stable")[:top]:
            host = [sp for sp in inner if sp[1] <= s[i] < sp[2]]
            name = min(host, key=lambda sp: sp[2] - sp[1])[0] if host \
                else "outside benchmark spans"
            out.append((name, float(e[i] - s[i]) * 1e-9))
        return out


def instruction(name: str) -> str:
    """The HLO instruction of an op event (``%fusion.12 = ...`` ->
    ``fusion.12``)."""
    return name.lstrip("%").split(" = ", 1)[0]


def op_family(name: str) -> str:
    """An op's instruction without its numeric suffix (``fusion``)."""
    return re.sub(r"\.\d+$", "", instruction(name))


def _events(line, origin_ns: int) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """An XLine's events as arrays: metadata id, start and end in ns after
    ``origin_ns``."""
    evs = line.events
    n = len(evs)
    mid = np.fromiter((e.metadata_id for e in evs), np.int64, n)
    off = np.fromiter((e.offset_ps for e in evs), np.int64, n)
    dur = np.fromiter((e.duration_ps for e in evs), np.int64, n)
    start = (line.timestamp_ns - origin_ns) + off * 1e-3
    return mid, start, start + dur * 1e-3


def reduce(log_dir: str, devices: Sequence) -> Trace:
    """Read the profiler's ``.xplane.pb`` under ``log_dir``."""
    from chipbench.xplane_pb2 import XSpace

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    space = XSpace()
    with open(paths[0], "rb") as f:
        space.ParseFromString(f.read())
    wanted = {f"/device:TPU:{d.id}" for d in devices}
    planes = [p for p in space.planes
              if p.name in wanted or p.name.startswith("/host")]
    stamps = [ln.timestamp_ns for p in planes for ln in p.lines if ln.events]
    origin = min(stamps) if stamps else 0
    names: Dict[str, int] = {}
    ops: Dict[str, Ops] = {}
    spans: list = []
    for plane in planes:
        meta = plane.event_metadata
        if plane.name in wanted:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                mid, start, end = _events(line, origin)
                ids, inv = np.unique(mid, return_inverse=True)
                # an HLO text loses its backend_config: a Pallas call
                # carries its whole kernel there
                ours = np.array([names.setdefault(
                    (meta[int(i)].name or meta[int(i)].display_name)
                    .split(", backend_config=", 1)[0], len(names))
                    for i in ids], np.int32)
                ops[plane.name] = Ops.build(ours[inv], start, end)
        else:
            bench = [i for i, m in meta.items()
                     if m.name.startswith(SPAN_PREFIX)]
            if not bench:
                continue
            for line in plane.lines:
                mid, start, end = _events(line, origin)
                for k in np.flatnonzero(np.isin(mid, bench)):
                    spans.append((meta[int(mid[k])].name, float(start[k]),
                                  float(end[k])))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no window span")
    if not ops:
        raise RuntimeError(f"the trace holds no {OPS_LINE!r} line for "
                           f"{sorted(wanted)}")
    return Trace(list(names), ops, spans, win[0])


_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_BYTES = {"pred": 1, "bf16": 2}


def _nbytes(dtype: str, dims: str) -> int:
    size = _BYTES.get(dtype) or int(dtype[1:]) // 8
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return size * n


def hlo_arrays(long_name: str) -> Tuple[List[Tuple[str, int]],
                                        List[Tuple[str, int]]]:
    """Result and operand arrays of one HLO instruction's text, as
    ``(shape text, bytes)``: results are the arrays before ``custom-call(``
    (or the op's own ``(``), operands those of ``operand_layout_constraints``
    when present, else of the argument list."""
    head, _, rest = long_name.partition("=")
    m = re.search(r"\s([a-z\-]+)\(", rest)
    result = rest[:m.start()] if m else rest
    cons = re.search(r"operand_layout_constraints=\{(.*?)\}\s*,\s*\w+=", rest)
    if cons:
        args = cons.group(1)
    else:
        args = rest[m.end():].split(")", 1)[0] if m else ""
    outs = [(f"{t}[{d}]", _nbytes(t, d)) for t, d in _ARRAY.findall(result)]
    ins = [(f"{t}[{d}]", _nbytes(t, d)) for t, d in _ARRAY.findall(args)]
    return outs, ins


def is_pallas(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def kernel_roofline(trace: "Trace", kernels: Dict, peaks: Dict
                    ) -> Optional[float]:
    """Percent: the least time the chip could take for the window's kernel
    calls (per call the larger of bytes over peak bandwidth and operations
    over peak rate, from the kernel models) over their summed device time.
    ``None`` when no call of a modelled kernel ran or none could be
    costed."""
    least_of = np.full(len(trace.names), np.nan)
    for i, text in enumerate(trace.names):
        if not is_pallas(text):
            continue
        outs, ins = hlo_arrays(text)
        name = instruction(text)
        for model in kernels.values():
            if model.matches(name) and (
                    not hasattr(model, "fits") or model.fits(outs, ins)):
                ops, nbytes = model.cost(outs, ins)
                least_of[i] = max(nbytes / peaks["hbm_bytes_per_s"],
                                  ops / peaks["int8_ops_per_s"])
                break
    least = spent = 0.0
    for ops in trace.ops.values():
        ops = ops.overlapping(*trace.window)
        per_call = least_of[ops.name]
        hit = ~np.isnan(per_call)
        least += float(per_call[hit].sum())
        spent += float((ops.end[hit] - ops.start[hit]).sum()) * 1e-9
    return 100.0 * least / spent if spent > 0 else None
