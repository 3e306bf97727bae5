"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1.*      — dataset characteristics (paper Table 1)
  * fig3.*        — extraction tasks vs the normalized-join baseline +
                    horizontal-scaling evidence (paper Figure 3)
  * flatten.*     — SCALPEL-Flattening throughput (paper §4)
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_table1() -> None:
    from benchmarks import table1_dataset

    for r in table1_dataset.run(n_patients=2_000):
        _emit(
            f"table1.{r['database']}",
            r["flatten_seconds"] * 1e6,
            f"rows={r['rows_central']}->{r['rows_denormalized']} "
            f"csv/columnar={r['csv_over_columnar']}x",
        )


def bench_fig3() -> None:
    from benchmarks import fig3_scaling

    for r in fig3_scaling.run_baseline(n_patients=2_000):
        _emit(
            f"fig3.baseline.{r['task']}",
            r["scalpel3_s"] * 1e6,
            f"normalized_join={r['normalized_join_s']}s speedup={r['speedup']}x",
        )
    for r in fig3_scaling.run_scaling(n_patients=2_000, shard_counts=(1, 2, 4)):
        if "error" in r:
            _emit(f"fig3.scaling.shards{r['shards']}", 0.0, "ERROR")
            continue
        _emit(
            f"fig3.scaling.{r['task']}.shards{r['shards']}",
            r["wall_s"] * 1e6,
            f"platform={r['platform']} "
            f"per_dev_bytes={r['per_device_bytes']:.3g} "
            f"per_dev_flops={r['per_device_flops']:.3g}",
        )


def bench_flattening() -> None:
    from benchmarks import flattening_bench

    for r in flattening_bench.run(n_patients=4_000):
        _emit(
            f"flatten.{r['database']}",
            r["flatten_s"] * 1e6,
            f"rows_per_s={r.get('rows_per_s')} mb_per_s={r.get('mb_per_s', '')}",
        )


def bench_flatten_plan(n_patients: int = 4_000, repeats: int = 5) -> None:
    """Plan-level Study.flatten vs eager flatten_star (parity-checked)."""
    from benchmarks import flattening_bench

    for r in flattening_bench.run_plan_vs_eager(n_patients=n_patients,
                                                repeats=repeats):
        _emit(
            f"flatten_plan.{r['database']}",
            r["plan_s"] * 1e6,
            f"eager_us={r['eager_s'] * 1e6:.1f} "
            f"plan/eager={r['plan_over_eager']} "
            f"cap={r['plan_capacity']}/{r['eager_capacity']} "
            f"parity={r['parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"flatten_plan.{r['database']}: plan/eager row-set parity "
                "FAILED — the plan path diverged from eager flatten_star")


def bench_pruning(n_patients: int = 2_000, repeats: int = 3) -> None:
    """Column pruning gate: the pruned plan must feed strictly fewer bytes
    into the flatten joins than the unpruned baseline (bytes-materialized
    proxy: sum of column sizes entering each join), with event parity.
    Emits ``BENCH_pruning.json`` next to the working directory."""
    import json

    from benchmarks import pruning_bench

    rows = pruning_bench.run(n_patients=n_patients, repeats=repeats)
    with open("BENCH_pruning.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _emit(
            f"pruning.{r['database']}",
            r["pruned_s"] * 1e6,
            f"join_bytes={r['join_bytes_pruned']}/{r['join_bytes_unpruned']} "
            f"reduction={r['reduction']} parity={r['parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"pruning.{r['database']}: pruned/unpruned event parity "
                "FAILED — column pruning changed extractor results")
        if r["join_bytes_pruned"] >= r["join_bytes_unpruned"]:
            raise SystemExit(
                f"pruning.{r['database']}: pruning did not reduce the bytes "
                f"materialized into the joins "
                f"({r['join_bytes_pruned']} >= {r['join_bytes_unpruned']})")


def bench_predicate(n_patients: int = 2_000, repeats: int = 3) -> None:
    """Fused-predicate gate: the Pallas Expr->bitset kernel must beat the
    jnp mask algebra on mask-pass bytes (bitset out = 1 bit/row vs bool out
    = 1 byte/row; column reads identical) for every fused_mask of the
    pipeline, with bit-identical extracted events.  Emits
    ``BENCH_predicate.json``."""
    import json

    from benchmarks import predicate_bench

    rows = predicate_bench.run(n_patients=n_patients, repeats=repeats)
    with open("BENCH_predicate.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _emit(
            f"predicate.{r['database']}",
            r["pallas_s"] * 1e6,
            f"jnp_us={r['jnp_s'] * 1e6:.1f} "
            f"mask_bytes={r['mask_bytes_pallas']}/{r['mask_bytes_jnp']} "
            f"reduction={r['reduction']} masks={r['fused_masks']} "
            f"parity={r['parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"predicate.{r['database']}: jnp/pallas event parity FAILED "
                "— the bitset kernel diverged from the jnp mask path")
        if r["mask_bytes_pallas"] >= r["mask_bytes_jnp"]:
            raise SystemExit(
                f"predicate.{r['database']}: fused kernel did not reduce "
                f"mask-pass bytes ({r['mask_bytes_pallas']} >= "
                f"{r['mask_bytes_jnp']})")


def bench_bitset(n_patients: int = 2_000, repeats: int = 3) -> None:
    """Bitset-native validity gate: the packed-word table layout must shrink
    the end-to-end mask-path validity bytes (predicate -> cohort ->
    compaction) vs the seed's bool-column baseline, with bit-identical
    extracted events across the jnp/pallas predicate engines.  Emits
    ``BENCH_bitset.json``."""
    import json

    from benchmarks import bitset_bench

    rows = bitset_bench.run(n_patients=n_patients, repeats=repeats)
    with open("BENCH_bitset.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _emit(
            f"bitset.{r['database']}",
            r["pallas_s"] * 1e6,
            f"jnp_us={r['jnp_s'] * 1e6:.1f} "
            f"mask_bytes={r['mask_bytes_bitset']}/{r['mask_bytes_bool']} "
            f"reduction={r['reduction']} nodes={r['mask_path_nodes']} "
            f"parity={r['parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"bitset.{r['database']}: jnp/pallas event parity FAILED "
                "— bitset-native validity diverged between mask engines")
        if r["mask_bytes_bitset"] >= r["mask_bytes_bool"]:
            raise SystemExit(
                f"bitset.{r['database']}: packed validity did not reduce "
                f"mask-path bytes ({r['mask_bytes_bitset']} >= "
                f"{r['mask_bytes_bool']})")


def bench_serving(n_patients: int = 2_000, n_queries: int = 32) -> None:
    """Cohort-query-service gate: under a mixed multi-tenant workload the
    service must (a) stay bit-identical to solo runs — local sync, local
    pipelined, AND sharded, (b) compile at most one executable per plan
    shape on both paths — vs one per query naively, (c) serve at least
    half the cacheable subgraphs from the cross-tenant cache, (d) beat the
    sequential naive wall-clock, (e) pipeline: the async submit/realize
    warm-serve wall must beat its own no-overlap accounting
    (submit_s + realize_s for the same timed serve — realization provably
    hidden behind submission; the measured synchronous wall is reported
    but not gated, as on the core-saturated CPU smoke host the wall race
    is noise — same caveat as ``bench_chunked``), and (f) record ZERO
    engine demotions — hoisted literals ride as Pallas kernel operands,
    for the served queries and the golden example plans alike.  Emits
    ``BENCH_serving.json``."""
    import json

    from benchmarks import serving_bench

    rows = serving_bench.run(n_patients=n_patients, n_queries=n_queries)
    with open("BENCH_serving.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _emit(
            f"serving.{r['name']}",
            r["service_total_s"] * 1e6,
            f"naive_s={r['naive_total_s']} "
            f"serve_s={r['service_serve_s']}/{r['service_sync_serve_s']} "
            f"speedup={r['speedup']}x pipeline={r['pipeline_speedup']}x "
            f"serve_overlap_s={r['serve_overlap_s']} "
            f"compiles={r['service_compiles']}/{r['naive_compiles']} "
            f"sharded_compiles={r['sharded_compiles']} "
            f"hit_rate={r['hit_rate']} p50={r['service_p50_s']}s "
            f"p95={r['service_p95_s']}s demotions={r['demotions']} "
            f"parity={r['parity']}/{r['sharded_parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"serving.{r['name']}: service/solo result parity FAILED — "
                "served queries diverged from solo Study.run")
        if r["sharded_parity"] != "pass":
            raise SystemExit(
                f"serving.{r['name']}: sharded service parity FAILED — "
                "shard_map-served queries diverged from solo Study.run")
        if not (r["service_compiles"] <= r["n_shapes"]
                < r["naive_compiles"]):
            raise SystemExit(
                f"serving.{r['name']}: shared-plan reuse did not cut "
                f"compiles ({r['service_compiles']} executables for "
                f"{r['n_queries']} queries vs naive {r['naive_compiles']})")
        if r["sharded_compiles"] > r["n_shapes"]:
            raise SystemExit(
                f"serving.{r['name']}: sharded path compiled "
                f"{r['sharded_compiles']} executables for "
                f"{r['n_shapes']} normalized shapes — plan-normalized "
                "sharing is broken under shard_map")
        if r["hit_rate"] < 0.5:
            raise SystemExit(
                f"serving.{r['name']}: subgraph-cache hit rate "
                f"{r['hit_rate']} < 0.5")
        if r["service_total_s"] >= r["naive_total_s"]:
            raise SystemExit(
                f"serving.{r['name']}: service wall-clock did not beat the "
                f"sequential naive path ({r['service_total_s']}s >= "
                f"{r['naive_total_s']}s)")
        if r["service_serve_s"] >= (r["serve_submit_s"]
                                    + r["serve_realize_s"]):
            raise SystemExit(
                f"serving.{r['name']}: async pipeline did not overlap — "
                f"warm-serve wall {r['service_serve_s']}s >= no-overlap "
                f"accounting {r['serve_submit_s']}s + "
                f"{r['serve_realize_s']}s; realization is not being "
                "hidden behind device submission")
        if r["demotions"] or r["golden_demotions"]:
            raise SystemExit(
                f"serving.{r['name']}: engine demotions recorded "
                f"(served={r['demotions']}, "
                f"golden={r['golden_demotions']}) — hoisted literals must "
                "stay on the Pallas kernel path")


def bench_chunked(n_patients: int = 2_000, repeats: int = 3) -> None:
    """Out-of-core gate: streaming the partitioned star through the chunked
    executor must (a) merge to a result bit-identical to the resident run —
    cohort words, event valid-rows, feature tensors, (b) compile exactly
    ONE executable for the whole chunk stream, and (c) overlap load with
    execution: pipelined wall < the same run's load_s + exec_s, the
    no-overlap accounting (the measured prefetch=False wall is reported
    but not gated — see ``chunked_bench`` docstring).  Emits
    ``BENCH_chunked.json``."""
    import json

    from benchmarks import chunked_bench

    rows = chunked_bench.run(n_patients=n_patients, repeats=repeats)
    with open("BENCH_chunked.json", "w") as f:
        json.dump(rows, f, indent=2)
    for r in rows:
        _emit(
            f"chunked.{r['name']}",
            r["pipelined_s"] * 1e6,
            f"serial_s={r['serial_s']} serial_run_s={r['serial_run_s']} "
            f"saved={r['overlap_saved_s']}s speedup={r['speedup']}x "
            f"chunks={r['n_chunks']} compiles={r['compiles']} "
            f"resident_s={r['resident_s']} parity={r['parity']}",
        )
        if r["parity"] != "pass":
            raise SystemExit(
                f"chunked.{r['name']}: chunked/resident parity FAILED — "
                "the merged chunk stream diverged from the resident run")
        if r["compiles"] != 1:
            raise SystemExit(
                f"chunked.{r['name']}: expected ONE compile across "
                f"{r['n_chunks']} chunks, saw {r['compiles']}")
        if r["pipelined_s"] >= r["serial_s"]:
            raise SystemExit(
                f"chunked.{r['name']}: prefetch overlap did not beat serial "
                f"load-then-execute accounting ({r['pipelined_s']}s wall >= "
                f"{r['serial_s']}s load+exec — the legs never overlapped)")


def bench_analyze() -> None:
    """Static-analysis gate: the golden example plans must be free of
    error/warn diagnostics under both predicate engines, and every seeded
    defect fixture must trip exactly its registered code — the same
    contract ``tools/plan_lint.py`` enforces, wired into the smoke run so
    a broken analyzer (or a newly-dirty golden plan) fails CI twice."""
    import time

    from repro.study.analyze import DIAGNOSTIC_CODES, analyze, \
        format_diagnostics
    from repro.study.defects import all_defects, golden_studies

    for name, study in golden_studies().items():
        for engine in ("pallas", "jnp"):
            plan = study.optimized_plan(predicate_engine=engine)
            t0 = time.perf_counter()
            diags = analyze(plan, n_patients=study.n_patients)
            us = (time.perf_counter() - t0) * 1e6
            bad = [d for d in diags if d.severity in ("error", "warn")]
            _emit(f"analyze.{name}.{engine}", us,
                  f"nodes={len(plan.nodes)} diags={len(diags)} "
                  f"error_warn={len(bad)}")
            if bad:
                raise SystemExit(
                    f"analyze.{name}.{engine}: golden plan carries "
                    f"error/warn diagnostics:\n{format_diagnostics(bad)}")
    missed = [code for code, plan, kwargs in all_defects()
              if not any(d.code == code for d in analyze(plan, **kwargs))]
    _emit("analyze.defects", 0.0,
          f"fired={len(DIAGNOSTIC_CODES) - len(missed)}"
          f"/{len(DIAGNOSTIC_CODES)}")
    if missed:
        raise SystemExit(
            f"analyze.defects: seeded defects not detected: {missed}")


def bench_spec(n: int = 24, n_patients: int = 300) -> None:
    """Declarative-front-end gate: a fixed-seed fuzz corpus must show 100%
    parity (every valid spec executes identically under jnp, pallas and the
    chunked path, emptiness verdicts cross-checked) and 100% rejection
    (every catalog mutation refused with its exact SPEC code); the golden
    wire specs must round-trip onto the golden plans under both engines.
    Emits ``BENCH_spec.json``."""
    import json
    import time

    from repro.study.defects import golden_studies
    from repro.study.fuzz import run_corpus
    from repro.study.spec import compile_spec, spec_from_study

    t0 = time.perf_counter()
    for name, study in golden_studies().items():
        rebuilt = compile_spec(spec_from_study(study))
        for engine in ("pallas", "jnp"):
            if (rebuilt.optimized_plan(predicate_engine=engine).key()
                    != study.optimized_plan(predicate_engine=engine).key()):
                raise SystemExit(
                    f"spec.roundtrip.{name}.{engine}: wire spec does not "
                    f"rebuild the golden plan")
    _emit("spec.roundtrip", (time.perf_counter() - t0) * 1e6,
          f"goldens={len(golden_studies())} engines=2")

    t0 = time.perf_counter()
    report = run_corpus(n=n, seed=0, n_patients=n_patients)
    dt = time.perf_counter() - t0
    with open("BENCH_spec.json", "w") as f:
        json.dump(dict(report.to_json(), elapsed_s=round(dt, 2)), f, indent=2)
    _emit("spec.fuzz", dt * 1e6 / max(1, n),
          f"n={report.n} valid={report.n_valid} mutated={report.n_mutated} "
          f"sp003={report.n_sp003} sp014={report.n_sp014} "
          f"gated={report.n_chunk_gated} failures={len(report.failures)}")
    if not report.ok:
        raise SystemExit("spec.fuzz: differential corpus failed:\n"
                         + report.summary())
    if report.n_valid + report.n_mutated != n:
        raise SystemExit(
            f"spec.fuzz: only {report.n_valid}+{report.n_mutated} of {n} "
            f"specs reached a verdict")


def bench_study(n_patients: int = 2_000, repeats: int = 8) -> None:
    from benchmarks import study_plan_bench

    for r in study_plan_bench.run(n_patients=n_patients, repeats=repeats):
        _emit(f"study_plan.{r['name']}", r["seconds"] * 1e6, r["derived"])


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small synthetic dataset, plan-executor coverage "
                    "only — the CI regression gate")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.smoke:
        bench_table1()
        bench_flatten_plan(n_patients=500, repeats=2)
        bench_pruning(n_patients=500, repeats=2)
        bench_predicate(n_patients=500, repeats=2)
        bench_bitset(n_patients=500, repeats=2)
        bench_study(n_patients=500, repeats=2)
        bench_serving(n_patients=500)
        bench_chunked(n_patients=500, repeats=2)
        bench_analyze()
        bench_spec(n=24, n_patients=300)
        return
    bench_table1()
    bench_flattening()
    bench_flatten_plan()
    bench_pruning()
    bench_predicate()
    bench_bitset()
    bench_fig3()
    bench_study()
    bench_serving()
    bench_chunked()
    bench_analyze()
    bench_spec()


if __name__ == "__main__":
    main()
