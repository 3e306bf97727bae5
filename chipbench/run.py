#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, chips, metrics) is read from
``BENCHMARK.json`` at the root of the checkout; the configuration file, the
traffic mix, the loop its ``kind`` names, the study design, the per-layer
metric readers and the kernel models are found by name under
``chipbench/``.  The run refuses a platform other than TPU and fewer chips
than the cell asks for, and then prints no result.

``--trace 0`` measures the cell's end-to-end metrics with the profiler off;
``--trace 1`` runs the part of the window the loop names (one study for the
closed study loop) under the profiler (which keeps at most 6 Mi op events)
and reports the per-layer metrics, the device's busy and window seconds and
a breakdown.
Every run checks its answers against the numpy reference once the window
has closed; the numbers compared, each with its limit, close standard error
and the result line (``checks``).
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import harness

    try:
        bench = harness.load_benchmark(ROOT)
        cell = harness.find_cell(bench, args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"chipbench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"sees {len(devs)} {devs[0].platform} device(s). No result.",
              file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    harness.run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                     devices=devs[:int(cell["chips"])])
    return 0


if __name__ == "__main__":
    sys.exit(main())
