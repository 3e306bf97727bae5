"""Quickstart: the SCALPEL3 pipeline in ~40 lines (paper Supplementary A).

  synthetic SNDS -> ONE lazy Study plan covering flattening (denormalization
  joins), extraction and cohort algebra, compiled into a single XLA program
  -> stats report.

The ``Study`` builder defers everything: ``flatten`` puts the star-schema
joins into the plan (capacities sized host-side from table statistics),
extractors chain onto the flat node and share a single projection, mask steps
fuse, each output materializes exactly once, and every executed plan node —
including per-join FlatteningStats — lands in the ``OperationLog``
automatically.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import DCIR_SCHEMA, drug_dispenses, medical_acts_dcir, stats
from repro.data.synthetic import SyntheticConfig, generate_dcir
from repro.compile_cache import enable_compile_cache
from repro.study import Study, column_audit_from_log, flow_rows_from_log

enable_compile_cache()

# 1. normalized claims data (stand-in for the CSV exports CNAM dumps)
cfg = SyntheticConfig(n_patients=1_000, seed=0)
dcir = generate_dcir(cfg)
print(f"normalized DCIR: {int(dcir['ER_PRS'].count)} cash-flow rows")

# 2-4. SCALPEL-Flattening + Extraction + Analysis as ONE lazy study plan
study = (Study(n_patients=cfg.n_patients)
         .flatten(DCIR_SCHEMA)                      # joins in the plan IR
         .extract(drug_dispenses(), name="drug_purchases")
         .extract(medical_acts_dcir(codes=list(range(30))), name="acts")
         .patients("IR_BEN")
         .cohort("base", "extract_patients")
         .cohort("drugged", "drug_purchases")
         .cohort("final", "drugged & base - acts")
         .flow("base", "drugged", "final"))

opt = study.optimized_plan(tables=dict(dcir))
ops = opt.count_ops()
print(f"\noptimized plan: {ops.get('scan_star', 0)} star-table scans, "
      f"{ops.get('lookup_join', 0)} joins, "
      f"{ops.get('fused_mask', 0)} fused masks, "
      f"{ops.get('compact', 0)} compactions")
# join-aware column pruning: once extractors chain onto the flat node, every
# dimension column no extractor reads is dropped BEFORE the joins — the
# narrowed scan projections are visible right in the plan
for n in opt.nodes:
    if n.op == "select" and n.get("pruned_columns"):
        print(f"  pruned scan -> keeps {list(n.get('cols'))}, "
              f"drops {list(n.get('pruned_columns'))}")

res = study.run(dict(dcir))                         # raw star tables in
res.assert_no_loss()                                # the paper's join audit
for i, d in sorted(res.flatten_stats.items()):
    print(f"  {d['stage']}: rows {d['rows_in']}->{d['rows_out']} "
          f"matched={d['matched']} overflow={d['overflow']}")
final = res.cohorts["final"]
print(f"\nfinal cohort: {final.subject_count()} subjects")
print(f"describe(): {final.describe()}")
print("\n" + res.flow.render())
print("\nflowchart rebuilt from the OperationLog alone:")
print(flow_rows_from_log(res.log))
print("\ncolumn audit (what each stage read) from the OperationLog alone:")
for r in column_audit_from_log(res.log)[:4]:
    print(f"  {r['stage']}: read={r['required_columns']} "
          f"pruned={r['pruned_columns']}")

# 5. automatic statistics report
pats = res.events["extract_patients"]
print("\n" + stats.report(final, pats, names=["gender_distribution", "age_buckets"]))
