"""The paper's §4 fractures-vs-exposures study over the SNDS star: its code
lists, the study as the program builds it, the same study in numpy, and
the comparison of the two.  The default design of the closed study loop
(``loops/closed_study.py``)."""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional

import numpy as np

from chipbench import traffic
from chipbench.data import snds
from chipbench.reference import snds_ref as ref
from repro.core import (DCIR_SCHEMA, PMSI_MCO_SCHEMA, diagnoses,
                        drug_dispenses, hospital_stays, medical_acts_dcir,
                        medical_acts_pmsi)
from repro.study import Study, col

#: the study's event outputs, compared row for row with the reference
EVENT_OUTPUTS = ("drug_purchases", "prevalent_drugs", "acts", "hospital_acts",
                 "diagnoses", "stays", "exposures", "all_acts", "fractures",
                 "follow_up")


def study_codes(seed: int, study: Mapping, zipf: float) -> Dict[str, np.ndarray]:
    """The study's code lists for this seed: the drugs of interest by
    popularity among the most popular codes, the fracture codes uniformly
    among codes of middling rank (an outcome is rare)."""
    r = traffic.rng_for(seed, 1)
    lo, hi = study["fracture_code_ranks"]

    def rare(n):
        return np.sort(r.choice(np.arange(lo, hi), size=n, replace=False)
                       ).astype(np.int32)

    return {"prevalent": traffic.popular_codes(
                r, int(study["prevalent_drugs"]), int(study["code_pool"]),
                zipf),
            "fracture_acts": rare(int(study["fracture_act_codes"])),
            "fracture_diags": rare(int(study["fracture_diag_codes"]))}


def codes(study: Mapping, zipf: float) -> Dict[str, np.ndarray]:
    """The code lists every run uses, whatever its seed: the study program
    bakes them in, so a list per seed would recompile it on every run."""
    return study_codes(snds.SIZES_SEED, study, zipf)


def build(spec: snds.StarSpec, study: Dict, codes: Dict) -> Study:
    """The §4 study over the raw star: DCIR flattened in time slices,
    PMSI-MCO flattened, six extractors, three transforms, cohort algebra,
    a flowchart and the two feature exports."""
    t0, t1 = spec.study_start, spec.study_end
    return (Study(n_patients=spec.n_patients, window=(t0, t1))
            .flatten(DCIR_SCHEMA, time_slices=int(study["dcir_time_slices"]),
                     time_column=study["time_column"], t0=t0, t1=t1)
            .flatten(PMSI_MCO_SCHEMA)
            .patients("IR_BEN")
            .extract(drug_dispenses(), name="drug_purchases")
            .extract(drug_dispenses().filtered(
                col("cip13").isin([int(c) for c in codes["prevalent"]])
                & col("execution_date").between(t0, t1)),
                name="prevalent_drugs")
            .extract(medical_acts_dcir(), name="acts")
            .extract(medical_acts_pmsi(), name="hospital_acts")
            .extract(diagnoses(), name="diagnoses")
            .extract(hospital_stays(), name="stays")
            .cohort("base", "extract_patients")
            .transform("exposures", "drug_purchases", name="exposures",
                       purview_days=int(study["purview_days"]))
            .concat("all_acts", "acts", "hospital_acts")
            .transform("fractures", "all_acts", "diagnoses", name="fractures",
                       fracture_act_codes=[int(c) for c in
                                           codes["fracture_acts"]],
                       fracture_diag_codes=[int(c) for c in
                                            codes["fracture_diags"]])
            .transform("follow_up", "extract_patients", "drug_purchases",
                       name="follow_up", study_end=t1)
            .cohort("exposed", "exposures")
            .cohort("fractured", "fractures")
            .cohort("final", "(exposed & base) - fractured")
            .flow("base", "exposed", "final")
            .featurize("X", cohort="final", kind="dense",
                       **study["dense"])
            .featurize("tokens", cohort="final", kind="tokens",
                       **study["tokens"]))


def reference(host: Dict, spec: snds.StarSpec, study: Dict, codes: Dict,
              lossy_capacity: Optional[float] = None) -> Dict:
    """The whole study in numpy.  ``lossy_capacity`` is the control: the
    PMSI 1:N joins keep at most that many output rows per input row (a
    capacity guessed from a slack factor instead of planned exactly)."""
    n = spec.n_patients
    t0, t1 = spec.study_start, spec.study_end
    dcir, s1 = ref.flatten_dcir_sliced(host, study["time_column"],
                                       int(study["dcir_time_slices"]), t0, t1)
    pmsi, s2 = ref.flatten_pmsi(host)
    if lossy_capacity is not None:
        cap = int(lossy_capacity * host["MCO_B"]["stay_id"].size)
        pmsi = {c: v[:cap] for c, v in pmsi.items()}
    ev = {}
    ev["extract_patients"] = ref.patients(host["IR_BEN"])
    ev["drug_purchases"] = ref.drug_purchases(dcir)
    ev["prevalent_drugs"] = ref.drug_purchases(dcir, codes["prevalent"],
                                               (t0, t1))
    ev["acts"] = ref.dcir_acts(dcir)
    ev["hospital_acts"] = ref.hospital_acts(pmsi)
    ev["diagnoses"] = ref.diagnoses(pmsi)
    ev["stays"] = ref.stays(pmsi)
    ev["exposures"] = ref.exposures(ev["drug_purchases"],
                                    int(study["purview_days"]))
    ev["all_acts"] = {c: np.concatenate([ev["acts"][c],
                                         ev["hospital_acts"][c]])
                      for c in ref.EVENT_COLUMNS}
    ev["fractures"] = ref.fractures(ev["all_acts"], ev["diagnoses"],
                                    codes["fracture_acts"],
                                    codes["fracture_diags"])
    ev["follow_up"] = ref.follow_up(ev["extract_patients"],
                                    ev["drug_purchases"], n, t1)
    base = ref.subjects(ev["extract_patients"], n)
    exposed = ref.subjects(ev["exposures"], n)
    fractured = ref.subjects(ev["fractures"], n)
    final = np.setdiff1d(np.intersect1d(exposed, base), fractured)
    cohorts = {"base": base, "exposed": exposed, "fractured": fractured,
               "final": final}
    flow = [base.size, np.intersect1d(base, exposed).size,
            np.intersect1d(np.intersect1d(base, exposed), final).size]
    fev = ref.checked(ref.keep_subjects(ev["exposures"], final), (t0, t1))
    d = study["dense"]
    X = ref.dense_features(fev, t0, d["n_buckets"], d["bucket_days"],
                           d["n_features"], n)
    toks, mask = ref.token_sequences(fev, int(study["tokens"]["seq_len"]), n)
    return {"events": ev, "stats": sorted(s1 + s2), "cohorts": cohorts,
            "flow": [int(x) for x in flow], "X": X, "tokens": toks,
            "mask": mask,
            "counts": {k: int(next(iter(v.values())).size)
                       for k, v in ev.items()}}


def compare(prog: Dict, want: Dict, n_patients: int) -> List[str]:
    """Names of the outputs where the program and the reference differ."""
    bad = []
    for k, t in want["events"].items():
        cols = sorted(t)
        n_got, got = ref.row_digest(prog["events"][k], cols)
        n_want, wanted = ref.row_digest(t, cols)
        if n_got != n_want:
            bad.append(f"events/{k}/rows")
        elif not np.array_equal(got, wanted):
            bad.append(f"events/{k}/values")
    if prog["stats"] != want["stats"]:
        bad.append("flatten_stats")
    for k, ids in want["cohorts"].items():
        if not np.array_equal(prog["cohorts"][k], ids):
            bad.append(f"cohorts/{k}")
    if prog["flow"] != want["flow"]:
        bad.append("flow")
    X = prog["features"]["X"]
    if X.shape != want["X"].shape or not np.array_equal(X, want["X"]):
        bad.append("features/X")
    toks, mask = prog["features"]["tokens"]
    if not np.array_equal(toks, want["tokens"]):
        bad.append("features/tokens")
    if not np.array_equal(mask, want["mask"]):
        bad.append("features/tokens_mask")
    if prog["counts"] != want["counts"]:
        bad.append("counts")
    return bad
