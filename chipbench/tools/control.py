"""Run each cell's control at the cell's own size and print what its check
reads.

    python chipbench/tools/control.py <workload> <seed> [<seed> ...]

The control is the numpy reference put in the program's place with one of
the configuration's guarantees broken: the PMSI-MCO 1:N joins keep at most
1.5 output rows per stay (a capacity guessed from a slack factor, as the
planner's exact capacities would be replaced by), so the flatten is no
longer lossless.  Its answers go through the cell's own comparison against
the true reference; ``correct`` has to come out false.  The data is made on
the device from each seed, as in a run.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

SLACK = 1.5


def study_control(cfg, seed):
    from chipbench import study_cell, traffic
    from chipbench.data import snds

    spec = snds.StarSpec.from_config(cfg)
    host = snds.host_copy(snds.generate(spec, seed))
    codes = traffic.study_codes(snds.SIZES_SEED, cfg["study"],
                                spec.zipf_exponent)
    want = study_cell.reference_study(host, spec, cfg["study"], codes)
    ctl = study_cell.reference_study(host, spec, cfg["study"], codes,
                                     lossy_capacity=SLACK)
    ctl["features"] = {"X": ctl["X"], "tokens": (ctl["tokens"],
                                                 ctl["mask"])}
    bad = study_cell.compare_study(ctl, want, spec.n_patients)
    return {"differing_outputs": len(bad), "which": bad}


def main() -> int:
    import jax
    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    bench = harness.load_benchmark(ROOT)
    cell = harness.find_cell(bench, sys.argv[1])
    cfg = harness.load_config(cell["config"])
    for seed in (int(s) for s in sys.argv[2:]):
        t0 = time.perf_counter()
        got = study_control(cfg, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": got,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
