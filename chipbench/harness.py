"""The data-driven harness: cell lookup, compile cache, set-up and window,
the trace, the per-layer readers and the result line.

Everything that belongs to one configuration, traffic mix, per-layer metric
or kernel lives in a file of its own that this module finds by name:

- ``chipbench/configs/<config>.json``
- ``chipbench/traffic/<traffic>.json`` (its ``kind`` picks the loop)
- ``chipbench/metrics/<metric>.py`` (``read(run) -> float | None``)
- ``chipbench/kernels/<kernel>.py`` (``TRACE_NAMES`` and ``cost(event)``)
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional


HERE = os.path.dirname(os.path.abspath(__file__))
#: a ``--trace 1`` run traces this much of its window at most, so a
#: closed-loop window traces one study: a study's sequential scans put
#: millions of op events in a trace, and the profiler keeps 6 Mi
TRACE_SECONDS = 0.0


def load_benchmark(root: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(name: str) -> Dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program (the study's eager host ops too), so that only a
    cell's first run in a checkout compiles."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _load_path(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_models() -> Dict[str, object]:
    return {os.path.basename(p)[:-3]: _load_path("kernels",
                                                 os.path.basename(p)[:-3])
            for p in sorted(glob.glob(os.path.join(HERE, "kernels", "*.py")))}


def load_peaks(device_kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


class CompileCount:
    """Backend compiles, counted from JAX's own compile-duration events."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += float(duration)


class Run:
    """What the per-layer readers read: the cell's driver (its spans and
    counts), the reduced trace and the kernel models."""

    def __init__(self, cell: Dict, driver, trace, kernels: Dict, peaks: Dict):
        self.cell = cell
        self.driver = driver
        self.trace = trace
        self.kernels = kernels
        self.peaks = peaks


def _driver(cfg: Dict, mix: Dict, seed: int):
    if mix["kind"] == "closed_study":
        from chipbench.study_cell import StudyCell
        return StudyCell(cfg, mix, seed)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(bench: Dict, cell: Dict, seed: int, seconds: float, trace: bool,
             devices, cfg: Optional[Dict] = None,
             mix: Optional[Dict] = None) -> Dict:
    """Set-up, window, metrics and check of one cell; prints the result
    line and returns it.  ``cfg`` and ``mix`` replace the cell's files (the
    tests run cells at a small size this way)."""
    from chipbench import traffic
    from chipbench import trace as trace_mod

    cfg = cfg if cfg is not None else load_config(cell["config"])
    mix = mix if mix is not None else traffic.load(cell["traffic"])
    compiles = CompileCount()
    t0 = time.perf_counter()
    driver = _driver(cfg, mix, seed)
    driver.setup(seconds)
    setup_s = time.perf_counter() - t0
    _say(f"set-up {setup_s:.3f} s, {compiles.n} compiles "
         f"({compiles.seconds:.3f} s)")

    n_before = compiles.n
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    try:
        if trace:
            with trace_mod.Tracer(tdir) as tracer:
                driver.window(min(seconds, TRACE_SECONDS))
        else:
            driver.window(seconds)
        in_window = compiles.n - n_before
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        reduced = None
        if trace:
            t_read = time.perf_counter()
            reduced = trace_mod.reduce(tdir, devices)
            _say(f"trace: profiler stopped in {tracer.stop_s:.3f} s, "
                 f"{sum(o.name.size for o in reduced.ops.values())} op "
                 f"events read in {time.perf_counter() - t_read:.3f} s")
            if reduced.full:
                _say("trace: the profiler's buffer filled and dropped ops; "
                     "the per-study metrics are left out")
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    _say(f"compiles inside the window: {in_window}")
    attempted, failed = driver.counts()
    _say(f"studies completed in the window: {attempted}")

    d0 = devices[0]
    if trace:
        t_metrics = time.perf_counter()
        run = Run(cell, driver, reduced, kernel_models(),
                  load_peaks(d0.device_kind))
        metrics = {}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = _load_path("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = reduced.breakdown()
        _say(f"trace: per-layer metrics and breakdown in "
             f"{time.perf_counter() - t_metrics:.3f} s")
    else:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        metrics = {}
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    # the check: the program's state is freed before the reference runs
    prog = driver.take_outputs()
    gc.collect()
    t_ref = time.perf_counter()
    want = driver.reference()
    checks = driver.check(prog, want)
    _say(f"reference and comparison {time.perf_counter() - t_ref:.3f} s")
    if getattr(driver, "differing", None):
        _say(f"differing: {driver.differing[:20]}")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        _say(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return result
