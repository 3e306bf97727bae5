"""Plan-snapshot golden tests: the optimized plans of the two example
pipelines (quickstart, cohort_study) are serialized — ops, wiring, predicate
engines + bitset layout, fused exprs, pruned/required columns — and diffed
against ``tests/goldens/*.json``.

Optimizer changes then surface as *reviewable golden updates* instead of
silent plan drift: a pass reordering, a lost fusion, a widened scan or a
dropped engine stamp shows up as a JSON diff in the PR.  Content-dependent
params (capacities, slack heuristics) are excluded — the goldens pin plan
*structure*, not synthetic-data statistics.

Regenerate intentionally with::

    REGEN_GOLDENS=1 PYTHONPATH=src python -m pytest tests/test_plan_goldens.py
"""
import json
import os

import pytest

from repro.core import DCIR_SCHEMA, PMSI_MCO_SCHEMA, diagnoses, \
    drug_dispenses, hospital_stays, medical_acts_dcir, medical_acts_pmsi
from repro.study import Study, col, cut_points, normalize
from repro.study.expr import render_param

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")

# structural params worth pinning; capacities/slacks stay out (they depend on
# synthetic table statistics, not on optimizer behavior)
_KEEP = (
    "source", "star", "partitioned_on", "cols", "pruned_columns",
    "required_columns", "engine", "bitset_block", "bitset_word", "left_key",
    "right_key", "prefix", "key", "col", "keys", "name", "fn", "category",
    "value_col", "start_col", "end_col", "group_col", "weight_col", "kind",
    "null_cols", "lo", "hi", "columns", "valid_layout",
)


def plan_snapshot(plan) -> dict:
    """JSON-stable structural view of an optimized plan."""
    nodes = []
    for n in plan.nodes:
        p = {}
        for k, v in n.params:
            if k == "expr":
                p[k] = render_param(v)
            elif k == "exprs":
                p[k] = [render_param(e) for e in v]
            elif k == "filters":
                p[k] = [[c, list(codes)] for c, codes in v]
            elif k in _KEEP and v is not None:
                p[k] = list(v) if isinstance(v, tuple) else v
        nodes.append({"op": n.op, "inputs": list(n.inputs), "params": p})
    return {"nodes": nodes, "outputs": dict(plan.outputs)}


def normal_snapshot(plan) -> dict:
    """Structural view of the *canonical* (service-shared) form of a plan:
    the alpha-renamed node graph with hoisted-literal slots rendered as
    ``?N``/``?setN`` placeholders, the extracted literal/vector params, and
    the subgraph-cache cut points.  Pins what the cohort-query service keys
    executables and cache entries on — normalization drift (a slot
    reordering, a lost hoist, a shifted cut) surfaces as a golden diff."""
    nplan = normalize(plan)
    snap = plan_snapshot(nplan.plan)
    snap["lits"] = [float(v) if isinstance(v, float) else v
                    for v in nplan.lits]
    snap["vecs"] = [list(v) for v in nplan.vecs]
    snap["cut_points"] = [[i, nplan.plan.nodes[i].op]
                          for i in cut_points(nplan.plan)]
    return snap


def _check(name: str, plan, snapshot=plan_snapshot) -> None:
    snap = snapshot(plan)
    path = os.path.join(GOLDEN_DIR, name)
    if os.environ.get("REGEN_GOLDENS"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
        return
    if not os.path.exists(path):
        pytest.fail(f"golden {name} missing — regenerate with REGEN_GOLDENS=1")
    with open(path) as f:
        want = json.load(f)
    # json round-trip normalization (tuples -> lists) for the comparison
    snap = json.loads(json.dumps(snap, sort_keys=True))
    assert snap == want, (
        f"optimized plan drifted from goldens/{name}.  If the change is "
        f"intentional, regenerate with REGEN_GOLDENS=1 and review the diff.")


def _quickstart_study() -> Study:
    """Mirror of examples/quickstart.py: flatten + 2 extractors + patients
    + cohort algebra + flow."""
    return (Study(n_patients=1_000)
            .flatten(DCIR_SCHEMA)
            .extract(drug_dispenses(), name="drug_purchases")
            .extract(medical_acts_dcir(codes=list(range(30))), name="acts")
            .patients("IR_BEN")
            .cohort("base", "extract_patients")
            .cohort("drugged", "drug_purchases")
            .cohort("final", "drugged & base - acts")
            .flow("base", "drugged", "final"))


def _cohort_study() -> Study:
    """Mirror of examples/cohort_study.py (flat sources, transformers,
    algebra with parens, featurize)."""
    STUDY_END = 14_600 + 3 * 365
    return (Study(n_patients=2_000, window=(14_600, STUDY_END))
            .patients("IR_BEN")
            .extract(drug_dispenses(), name="drug_purchases")
            .extract(drug_dispenses()
                     .filtered(col("cip13").isin(range(65))
                               & col("execution_date").between(14_600,
                                                               STUDY_END)),
                     name="prevalent_drugs")
            .extract(medical_acts_dcir(), name="acts")
            .extract(medical_acts_pmsi(), name="hospital_acts")
            .extract(diagnoses(), name="diagnoses")
            .extract(hospital_stays(), name="stays")
            .transform("exposures", "drug_purchases", name="exposures",
                       purview_days=60)
            .concat("all_acts", "acts", "hospital_acts")
            .transform("fractures", "all_acts", "diagnoses", name="fractures",
                       fracture_act_codes=list(range(30)),
                       fracture_diag_codes=list(range(40)))
            .transform("follow_up", "extract_patients", "drug_purchases",
                       name="follow_up", study_end=STUDY_END)
            .cohort("base", "extract_patients")
            .cohort("exposed", "exposures")
            .cohort("fractured", "fractures")
            .cohort("final", "(exposed & base) - fractured")
            .flow("base", "exposed", "final")
            .featurize("X", cohort="final", kind="dense",
                       n_buckets=36, bucket_days=31, n_features=128)
            .featurize("tokens", cohort="final", kind="tokens", seq_len=256))


# predicate_engine is pinned explicitly ("auto" would make goldens
# backend-dependent); "pallas" also pins the engine + bitset-layout stamps.
def test_quickstart_plan_golden():
    _check("quickstart_plan.json",
           _quickstart_study().optimized_plan(predicate_engine="pallas"))


def test_quickstart_plan_golden_jnp_engine():
    _check("quickstart_plan_jnp.json",
           _quickstart_study().optimized_plan(predicate_engine="jnp"))


def test_cohort_study_plan_golden():
    _check("cohort_study_plan.json",
           _cohort_study().optimized_plan(predicate_engine="pallas"))


def test_quickstart_normal_golden():
    _check("quickstart_normal.json",
           _quickstart_study().optimized_plan(predicate_engine="jnp"),
           snapshot=normal_snapshot)


def test_cohort_study_normal_golden():
    _check("cohort_study_normal.json",
           _cohort_study().optimized_plan(predicate_engine="jnp"),
           snapshot=normal_snapshot)


def test_normal_snapshot_hoists_and_renames():
    """The canonical form must be literal-free and label-free: every literal
    lives in the params vectors (rendered as ?N slots), tenant-chosen names
    are alpha-renamed, and two literal-variants share one snapshot."""
    mk = lambda codes: (Study(n_patients=1_000)
                        .flatten(DCIR_SCHEMA)
                        .extract(medical_acts_dcir(codes=codes), name="acts"))
    a = normal_snapshot(mk(list(range(30))).optimized_plan(
        predicate_engine="jnp"))
    b = normal_snapshot(mk(list(range(100, 130))).optimized_plan(
        predicate_engine="jnp"))
    assert a["vecs"] == [list(range(30))]
    # same-length code lists share one structure (the vector is a traced
    # argument; its *length* is shape, hence structural)
    a.pop("vecs"), b.pop("vecs")
    assert a == b
    rendered = json.dumps(a)
    assert "?set0" in rendered          # hoisted isin slot, not inline codes
    assert "acts" not in rendered       # label stripped
    assert a["cut_points"], "canonical plan should expose cache cut points"


def test_snapshot_captures_engines_and_pruning():
    """The snapshot itself must carry the audit fields the goldens exist to
    pin: predicate engines + bitset layout and pruned scan projections."""
    snap = plan_snapshot(
        _quickstart_study().optimized_plan(predicate_engine="pallas"))
    ops = [n["op"] for n in snap["nodes"]]
    assert "fused_mask" in ops and "scan_star" in ops
    masks = [n for n in snap["nodes"] if n["op"] == "fused_mask"]
    assert all(m["params"].get("engine") == "pallas" for m in masks)
    from repro.kernels.predicate import DEFAULT_BLOCK

    assert all(m["params"].get("bitset_block") == DEFAULT_BLOCK
               for m in masks)
    # bitset-native validity: predicate + compact nodes carry the layout
    # stamp, and the pruned-to-key IR_BEN join is eliminated to a key_count
    layered = [n for n in snap["nodes"] if n["op"] in ("fused_mask", "compact")]
    assert layered and all(
        n["params"].get("valid_layout") == "bitset_u32" for n in layered)
    assert "key_count" in ops
    pruned = [n for n in snap["nodes"]
              if n["op"] == "select" and n["params"].get("pruned_columns")]
    assert pruned, "quickstart plan should prune unused dimension columns"
