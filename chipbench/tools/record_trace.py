"""Record a small traced study on the chip and keep it as a test fixture.

    python chipbench/tools/record_trace.py [out.json.gz]

Runs the ``study.batch`` cell for a two-second traced window (one study)
through the harness (result line and all) and writes 40 ms of the study
(``chipbench.trace.Trace.dump``), where its first predicate kernels and a
run of sorts fall, for ``tests/test_bench_trace.py``.  Needs a TPU.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

LO_MS, HI_MS = 1770.0, 1810.0


def small_window(t, lo_ms: float, hi_ms: float):
    """The part of a recorded study between ``lo_ms`` and ``hi_ms`` after
    its start, as its own window (a whole study holds millions of op
    events: the fracture scan's per-step slices)."""
    s0 = t.window[0]
    return t.cut(s0 + lo_ms * 1e6, s0 + hi_ms * 1e6)


def main() -> int:
    import jax
    from chipbench import harness
    from chipbench import trace as T

    out = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "trace_small.json.gz")
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    kept, real_reduce = [], T.reduce

    def keep(log_dir, devices):
        t = real_reduce(log_dir, devices)
        kept.append(t)
        return t

    T.reduce = keep
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, "study.batch")
    harness.enable_compile_cache(ROOT)
    harness.run_cell(bench, cell, 20_251_017, 2.0, True, jax.devices()[:1])
    small = small_window(kept[0], LO_MS, HI_MS)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    small.dump(out)
    print(f"wrote {out}: {sum(o.name.size for o in small.ops.values())} op "
          f"events, {len(small.spans)} spans, window {small.window_s:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
