"""Static plan verifier: every diagnostic code fires on its seeded defect,
clean plans stay clean, analysis is sound w.r.t. execution (SP003 "provably
empty" really means zero rows), the cohort-query service rejects error plans
before compiling, and the diagnostic surface of the golden example plans is
pinned as a reviewable JSON golden.

Regenerate diag goldens intentionally with::

    REGEN_GOLDENS=1 PYTHONPATH=src python -m pytest tests/test_analyze.py
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import hypothesis.strategies as st
from hypothesis import given, settings
from repro.core import DCIR_SCHEMA, drug_dispenses, medical_acts_dcir
from repro.core.columnar import ColumnarTable
from repro.data.synthetic import SyntheticConfig, generate_dcir
from repro.study import (
    CohortQueryService, DIAGNOSTIC_CODES, PlanValidationError, ServiceConfig,
    Study, analyze, col, execute, normalize,
)
from repro.study.analyze import errors, format_diagnostics
from repro.study.defects import DEFECTS, all_defects, golden_studies
from repro.study.optimizer import assign_engines
from repro.study.plan import PlanBuilder

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "goldens")

CFG = SyntheticConfig(n_patients=200, seed=7)


@pytest.fixture(scope="module")
def dcir():
    return generate_dcir(CFG)


# ---------------------------------------------------------------------------
# the defect matrix: every registered code fires on its seeded fixture
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("code", sorted(DEFECTS))
def test_seeded_defect_fires(code):
    plan, kwargs = DEFECTS[code]()
    diags = analyze(plan, **kwargs)
    hit = [d for d in diags if d.code == code]
    assert hit, (f"{code} did not fire on its seeded defect; got:\n"
                 + (format_diagnostics(diags) or "(clean)"))
    want_sev, _ = DIAGNOSTIC_CODES[code]
    # severity may escalate above the registered baseline (e.g. SP007 word
    # misalignment becomes an error when it breaks the shard quantum) but
    # never soften below it
    rank = {"info": 0, "warn": 1, "error": 2}
    assert all(rank[d.severity] >= rank[want_sev] for d in hit)
    assert all(d.message for d in hit)


def test_defect_registry_covers_every_code():
    assert set(DEFECTS) == set(DIAGNOSTIC_CODES)


def test_golden_studies_are_clean():
    """The two example pipelines carry no error/warn diagnostics under
    either predicate engine — the plan-lint CI gate's contract."""
    for name, study in golden_studies().items():
        for engine in ("pallas", "jnp"):
            plan = study.optimized_plan(predicate_engine=engine)
            diags = analyze(plan, n_patients=study.n_patients)
            bad = [d for d in diags if d.severity in ("error", "warn")]
            assert not bad, (f"{name}/{engine}:\n"
                             + format_diagnostics(bad))


# ---------------------------------------------------------------------------
# soundness: analysis verdicts agree with actual execution
# ---------------------------------------------------------------------------
_CMP = {"<": lambda c, v: c < v, "<=": lambda c, v: c <= v,
        ">": lambda c, v: c > v, ">=": lambda c, v: c >= v,
        "==": lambda c, v: c == v}


def _conjunct_plan(conjs):
    b = PlanBuilder()
    t = b.scan("T")
    expr = None
    for op, v in conjs:
        c = _CMP[op](col("x"), v)
        expr = c if expr is None else (expr & c)
    m = b.predicate(t, expr)
    b.set_output("out", b.compact(m))
    return b.build()


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(sorted(_CMP)),
                          st.integers(-4, 19)),
                min_size=1, max_size=4))
def test_interval_analysis_sound_vs_execution(conjs):
    """Random comparison conjuncts over a known int column: the analyzer
    must never call a satisfiable predicate empty (if SP003 fires, execution
    provably yields zero rows), and clean plans must execute."""
    plan = _conjunct_plan(conjs)
    tbl = ColumnarTable.from_columns(
        {"x": jnp.arange(16, dtype=jnp.int32),
         "patient_id": jnp.arange(16, dtype=jnp.int32)})
    diags = analyze(plan, tables={"T": tbl})
    assert not any(d.code in ("SP001", "SP002", "SP012", "SP013")
                   for d in diags)
    vals = execute(plan, {"T": tbl}, jit=False)
    out = vals[plan.output_ids["out"]]
    if any(d.code == "SP003" for d in diags):
        assert int(out.count) == 0, (
            "SP003 claimed always-false but rows survived:\n"
            + format_diagnostics(diags))


def test_contradiction_marks_output_empty():
    plan = _conjunct_plan([("<", 3), (">", 5)])
    diags = analyze(plan)
    assert {d.code for d in diags} >= {"SP003", "SP014"}


def test_errors_helper_and_formatting():
    plan, kwargs = DEFECTS["SP003"]()
    diags = analyze(plan, **kwargs)
    errs = errors(diags)
    assert errs and all(d.severity == "error" for d in errs)
    text = format_diagnostics(diags)
    assert "SP003" in text and "node" in text


# ---------------------------------------------------------------------------
# Study.check(): the user-facing entry point
# ---------------------------------------------------------------------------
def _bad_study(n_patients):
    s = Study(n_patients=n_patients)
    s.flatten(DCIR_SCHEMA)
    s.extract(medical_acts_dcir(), name="acts")
    s.filter("acts", (col("value") < 3) & (col("value") > 5), name="never")
    s.cohort("bad", "never")
    return s


def _good_study(n_patients):
    s = Study(n_patients=n_patients)
    s.flatten(DCIR_SCHEMA)
    s.extract(drug_dispenses(codes=list(range(40))), name="drugs")
    s.filter("drugs", col("value") >= 1, name="hi")
    s.cohort("base", "hi")
    return s


def test_study_check_flags_defect(dcir):
    diags = _bad_study(CFG.n_patients).check(tables=dict(dcir))
    codes = {d.code for d in diags if d.severity == "error"}
    assert "SP003" in codes


def test_study_check_clean(dcir):
    diags = _good_study(CFG.n_patients).check(tables=dict(dcir))
    assert not [d for d in diags if d.severity in ("error", "warn")], \
        format_diagnostics(diags)


# ---------------------------------------------------------------------------
# normalize() demotion audit: hoisted literals are kernel operands now, so
# demotion is the *exception* (kernel-infeasible stamps only)
# ---------------------------------------------------------------------------
def test_normalize_keeps_hoisted_literals_on_pallas():
    b = PlanBuilder()
    t = b.scan("T")
    m = b.predicate(t, col("x") > 5)          # inline literal -> hoisted
    b.set_output("out", b.compact(m))
    plan = assign_engines(b.build(), predicate_engine="pallas")
    nplan = normalize(plan)
    assert nplan.demoted == (), \
        "hoisted-literal pallas predicates must keep the kernel engine"
    pred = [n for n in nplan.plan.nodes if n.op == "predicate"]
    assert pred and all(n.get("engine") == "pallas" for n in pred)
    # literal-free predicates stay pallas and record nothing either
    b2 = PlanBuilder()
    t2 = b2.scan("T")
    m2 = b2.predicate(t2, col("x").not_null())
    b2.set_output("out", b2.compact(m2))
    n2 = normalize(assign_engines(b2.build(), predicate_engine="pallas"))
    assert n2.demoted == ()


def test_normalize_demotes_kernel_infeasible_stamp():
    # force-stamp pallas onto an isin past the SMEM operand budget (the
    # optimizer itself would stamp jnp) — the one case that still demotes
    from repro.kernels.predicate import MAX_ISIN_VALUES
    from repro.study.expr import as_param

    b = PlanBuilder()
    t = b.scan("T")
    m = b.add("predicate", (t,),
              expr=as_param(col("x").isin(range(MAX_ISIN_VALUES + 1))),
              engine="pallas", bitset_block=1024, bitset_word="uint32")
    b.set_output("out", b.compact(m))
    nplan = normalize(b.build())
    assert nplan.demoted, "oversized-whitelist pallas stamp must demote"
    for nid in nplan.demoted:
        assert nplan.plan.nodes[nid].get("engine") == "jnp"


# ---------------------------------------------------------------------------
# service integration: admission-time rejection + demotion accounting
# ---------------------------------------------------------------------------
def test_service_rejects_error_plan_before_compile(dcir):
    svc = CohortQueryService(dict(dcir), config=ServiceConfig())
    bad = svc.submit(_bad_study(CFG.n_patients), tenant="t1")
    svc.drain()
    assert bad.status == "invalid"
    assert isinstance(bad.error, PlanValidationError)
    assert any(d.code == "SP003" for d in bad.error.diagnostics)
    assert svc.stats.plans_rejected == 1
    assert svc.stats.tenant("t1").invalid == 1
    assert svc.stats.compile_count == 0, \
        "rejected plan must never reach the compile cache"
    assert any(e["op"] == "service:invalid:t1" for e in svc.log.entries)
    # a healthy study from another tenant still serves afterwards
    ok = svc.submit(_good_study(CFG.n_patients), tenant="t2")
    svc.drain()
    assert ok.status == "done"
    assert svc.stats.compile_count >= 1


def test_service_serves_pallas_without_demotions(dcir):
    # no-demotion regression: hoisted literals ride as kernel operands, so
    # a pallas-engine service keeps every predicate on the kernel path and
    # the demotion audit stays silent
    svc = CohortQueryService(
        dict(dcir), config=ServiceConfig(predicate_engine="pallas"))
    t = svc.submit(_good_study(CFG.n_patients), tenant="a")
    svc.drain()
    assert t.status == "done", t.error
    assert svc.stats.demotions == 0
    assert svc.stats.tenant("a").demoted == 0
    assert not [e for e in svc.log.entries
                if e["op"].startswith("service:demote:")]
    snap = svc.stats.snapshot()
    assert snap["demotions"] == 0
    assert snap["plans_rejected"] == 0


# ---------------------------------------------------------------------------
# diag goldens: the diagnostic surface of the example plans is pinned
# ---------------------------------------------------------------------------
def _diag_snapshot(study):
    plan = study.optimized_plan(predicate_engine="pallas")
    diags = analyze(plan, n_patients=study.n_patients)
    return [dataclasses.asdict(d) for d in diags]


def _check_diag_golden(name, study):
    snap = _diag_snapshot(study)
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
    snap = json.loads(json.dumps(snap, sort_keys=True))
    assert snap == want, (
        f"diagnostic surface drifted from goldens/{name}.  If intentional, "
        f"regenerate with REGEN_GOLDENS=1 and review the diff.")


def test_quickstart_diag_golden():
    _check_diag_golden("quickstart_diag.json", golden_studies()["quickstart"])


def test_cohort_study_diag_golden():
    _check_diag_golden("cohort_study_diag.json",
                       golden_studies()["cohort_study"])
