"""Full observational study: fractures vs drug exposures (the paper's §4
evaluation tasks (a)-(g) composed into the Supplementary-A study) — written
against the lazy ``Study`` builder.

One declaration covers both sub-databases: every DCIR extractor shares one
scan of the DCIR flat table (same for PMSI), transformers and cohort algebra
ride the same plan, provenance is logged automatically, and the whole study
executes as one jit-compiled program per source-table spec.

Run:  PYTHONPATH=src python examples/cohort_study.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import (
    DCIR_SCHEMA, PMSI_MCO_SCHEMA, diagnoses, drug_dispenses, flatten_star,
    hospital_stays, medical_acts_dcir, medical_acts_pmsi, stats,
)
from repro.data.synthetic import SyntheticConfig, generate_snds
from repro.compile_cache import enable_compile_cache
from repro.study import Study, col

enable_compile_cache()

cfg = SyntheticConfig(n_patients=2_000, seed=42)
P = cfg.n_patients
STUDY_END = 14_600 + 3 * 365
dcir, pmsi = generate_snds(cfg)

flat_dcir, _ = flatten_star(DCIR_SCHEMA, dcir)
flat_pmsi, _ = flatten_star(PMSI_MCO_SCHEMA, pmsi)

# -- tasks (a)-(g) as one lazy plan -------------------------------------------
# Predicates are typed column expressions (``col()``/``Expr``): the engine
# sees exactly which columns each step reads (fusing them into one mask pass
# per scan branch and pruning everything else), instead of opaque callables.
study = (Study(n_patients=P, window=(14_600, STUDY_END))
         .patients("IR_BEN")                                       # (a)
         .extract(drug_dispenses(), name="drug_purchases")         # (b)
         .extract(drug_dispenses()                                 # (c)
                  .filtered(col("cip13").isin(range(65))
                            & col("execution_date").between(14_600, STUDY_END)),
                  name="prevalent_drugs")
         .extract(medical_acts_dcir(), name="acts")                # (e) outpatient
         .extract(medical_acts_pmsi(), name="hospital_acts")       # (e) inpatient
         .extract(diagnoses(), name="diagnoses")                   # (f)
         .extract(hospital_stays(), name="stays")
         .transform("exposures", "drug_purchases", name="exposures",
                    purview_days=60)                               # (d)
         .concat("all_acts", "acts", "hospital_acts")
         .transform("fractures", "all_acts", "diagnoses", name="fractures",
                    fracture_act_codes=list(range(30)),
                    fracture_diag_codes=list(range(40)))           # (g)
         .transform("follow_up", "extract_patients", "drug_purchases",
                    name="follow_up", study_end=STUDY_END)
         # -- study assembly (Supplementary In[5]) ----------------------------
         # cohort algebra has a real parser now: & binds tighter than | and
         # -, parentheses group — the grouping below is explicit
         .cohort("base", "extract_patients")
         .cohort("exposed", "exposures")
         .cohort("fractured", "fractures")
         .cohort("final", "(exposed & base) - fractured")
         .flow("base", "exposed", "final")
         # -- ML export (FeatureDriver) ---------------------------------------
         .featurize("X", cohort="final", kind="dense",
                    n_buckets=36, bucket_days=31, n_features=128)
         .featurize("tokens", cohort="final", kind="tokens", seq_len=256))

opt = study.optimized_plan()
ops = opt.count_ops()
print(f"plan: {len(opt.nodes)} nodes, scans={ops.get('scan')}, "
      f"fused_masks={ops.get('fused_mask')}, compactions={ops.get('compact')}")

res = study.run({"DCIR": flat_dcir, "PMSI_MCO": flat_pmsi,
                 "IR_BEN": dcir["IR_BEN"]})

print("cohorts:", set(res.cohorts))
final = res.cohorts["final"]
print(f"\nIn [5]: exposed ∩ base \\ fractured -> {final.subject_count()} subjects")
print(f"Out[6]: {final.describe()!r}")
print("\nflowchart:\n" + res.flow.render())

pats = res.events["extract_patients"]
for stage in res.flow.steps:
    d = stats.distribution_by_gender_age_bucket(stage, pats)
    print(f"\n[{stage.name}] gender x age-decade:")
    print("  male  ", d["male"])
    print("  female", d["female"])

X = res.features["X"]
toks, mask = res.features["tokens"]
print(f"\ndesign matrix: {X.shape}, nnz={int((np.asarray(X) > 0).sum())}")
print(f"token corpus:  {toks.shape}, checks={res.feature_checks['tokens']}")
print(f"\nprovenance: {len(res.log.entries)} auto-logged operations "
      f"(commit {res.log.commit[:12]})")
