"""Typed column-expression IR: the ``col()``/``Expr`` DSL, the recursive-
descent cohort-algebra parser, predicate fusion parity (fused single-pass
mask vs naive per-node evaluation), and join-aware column pruning (the
acceptance criterion: dimension columns no extractor reads are dropped from
the star scans before the first join, with identical end-to-end results)."""
import numpy as np
import pytest
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import DCIR_SCHEMA, PMSI_MCO_SCHEMA, drug_dispenses, \
    flatten_star, medical_acts_dcir
from repro.core.columnar import ColumnarTable, NULL_INT
from repro.core.extraction import Extractor
from repro.data.synthetic import SyntheticConfig, generate_dcir, generate_pmsi
from repro.study import (
    PlanBuilder, Study, col, execute, expr_from_param, lit, optimize,
    parse_cohort_expr, column_audit_from_log,
)
from repro.study.expr import CohortCombine, CohortRef, node_predicate

CFG = SyntheticConfig(n_patients=200, seed=11)


@pytest.fixture(scope="module")
def dcir():
    return generate_dcir(CFG)


@pytest.fixture(scope="module")
def pmsi():
    return generate_pmsi(CFG)


def _table(**cols):
    arrs = {}
    for k, v in cols.items():
        a = np.asarray(v)
        arrs[k] = a.astype(np.float32 if a.dtype.kind == "f" else np.int32)
    return ColumnarTable.from_columns(arrs)


# ---------------------------------------------------------------------------
# Expr DSL basics
# ---------------------------------------------------------------------------
def test_expr_required_columns_and_roundtrip():
    e = ((col("a") + 1 >= col("b") * 2) & col("c").isin([1, 2, 3])
         | ~col("d").is_null())
    assert e.required_columns() == {"a", "b", "c", "d"}
    p = e.to_param()
    assert expr_from_param(p).to_param() == p      # stable serialization
    hash(p)                                        # plan params must hash


def test_expr_evaluate_matches_numpy():
    t = _table(a=[1, 5, int(NULL_INT), 7], b=[2, 2, 2, 2])
    m = np.asarray(((col("a") >= 3) & (col("a") % 2 == 1)
                    & col("a").not_null()).mask(t))
    assert m.tolist() == [False, True, False, True]
    m2 = np.asarray((col("a").between(1, 6) | (col("b") == 7)).mask(t))
    # NULL sentinel compares raw (document: use is_null for sentinel tests)
    assert m2.tolist() == [True, True, False, False]
    assert np.asarray(col("a").isin([]).mask(t)).tolist() == [False] * 4


def test_expr_rejects_python_bool_context():
    with pytest.raises(TypeError):
        bool(col("a") == 1)
    with pytest.raises(TypeError):
        col("a") == "strings-are-not-literals"


def test_predicate_node_in_plan_matches_naive(dcir):
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    e = (col("cip13").not_null() & (col("execution_date") >= 14_700)
         & ~col("prestation_code").isin([1000, 1001]))
    b = PlanBuilder()
    t = b.predicate(b.scan("DCIR"), e)
    out = b.set_output("out", b.compact(t))
    got = execute(b.build(), {"DCIR": flat})[out].to_numpy()
    want = flat.filter(e.mask(flat)).compact().to_numpy()
    for k in want:
        assert (got[k] == want[k]).all(), k


def test_extractor_where_predicate(dcir):
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    base = drug_dispenses()
    filt = base.filtered(col("execution_date") >= 14_800)
    assert "execution_date" in filt.projection()
    ev, ev_all = filt(flat).to_numpy(), base(flat).to_numpy()
    assert len(ev["start"]) < len(ev_all["start"])
    assert (ev["start"] >= 14_800).all()


def test_study_filter_output(dcir):
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    res = (Study(n_patients=CFG.n_patients)
           .extract(drug_dispenses(), name="drugs")
           .filter("drugs", col("start") >= 14_800, name="recent")
           .run({"DCIR": flat}))
    all_ev, recent = res.events["drugs"].to_numpy(), res.events["recent"].to_numpy()
    want = all_ev["start"][all_ev["start"] >= 14_800]
    assert sorted(recent["start"].tolist()) == sorted(want.tolist())


def test_node_predicate_reexpresses_legacy_ops():
    b = PlanBuilder()
    t = b.add("drop_nulls", (b.scan("T"),), cols=("x", "y"))
    v = b.add("value_filter", (t,), col="x", codes=(1, 2))
    s = b.slice_time(v, "d", 10, 20)
    plan_b = b
    nodes = plan_b.build().nodes
    assert node_predicate(nodes[t]).required_columns() == {"x", "y"}
    assert node_predicate(nodes[v]).required_columns() == {"x"}
    assert node_predicate(nodes[s]).required_columns() == {"d"}
    tbl = _table(x=[1, 3, int(NULL_INT)], y=[1, 1, 1], d=[12, 5, 15])
    assert np.asarray(node_predicate(nodes[v]).mask(tbl)).tolist() == \
        [True, False, False]
    assert np.asarray(node_predicate(nodes[s]).mask(tbl)).tolist() == \
        [True, False, True]


# ---------------------------------------------------------------------------
# fused path vs naive per-node evaluation (property)
# ---------------------------------------------------------------------------
def _random_pred(draw, depth: int):
    cols = ("a", "b", "c")
    if depth <= 0 or draw(st.integers(0, 2)) == 0:
        c = col(cols[draw(st.integers(0, 2))])
        kind = draw(st.integers(0, 3))
        if kind == 0:
            op = draw(st.sampled_from(["==", "!=", "<", "<=", ">", ">="]))
            rhs = lit(draw(st.integers(-5, 15)))
            return {"==": c.__eq__, "!=": c.__ne__, "<": c.__lt__,
                    "<=": c.__le__, ">": c.__gt__, ">=": c.__ge__}[op](rhs)
        if kind == 1:
            vals = draw(st.lists(st.integers(-5, 15), max_size=5))
            return c.isin(vals)
        if kind == 2:
            return c.is_null() if draw(st.booleans()) else c.not_null()
        return (c + draw(st.integers(0, 3))) % 4 == draw(st.integers(0, 3))
    k = draw(st.integers(0, 2))
    l = _random_pred(draw, depth - 1)
    if k == 0:
        return ~l
    r = _random_pred(draw, depth - 1)
    return (l & r) if k == 1 else (l | r)


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_property_fused_equals_naive(data):
    """A chain of random predicates executed through the optimizer (fused
    into ONE fused_mask, single-pass conjunction) must keep exactly the rows
    the naive per-node Expr evaluation keeps."""
    draw = data.draw
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n = draw(st.integers(1, 64))
    vals = rng.integers(-5, 15, n)
    vals[rng.random(n) < 0.2] = int(NULL_INT)
    t = _table(id=np.arange(n), a=vals, b=rng.integers(-5, 15, n),
               c=rng.integers(-5, 15, n))
    exprs = [_random_pred(draw, draw(st.integers(0, 2)))
             for _ in range(draw(st.integers(1, 3)))]

    b = PlanBuilder()
    nid = b.scan("T")
    for e in exprs:
        nid = b.predicate(nid, e)
    out = b.set_output("out", b.compact(nid))
    opt = optimize(b.build())
    assert opt.count_ops().get("fused_mask", 0) == 1   # chain fused to one
    got = execute(opt, {"T": t})[opt.output_ids["out"]].to_numpy()["id"]

    naive = t
    for e in exprs:
        naive = naive.filter(e.mask(naive))
    want = naive.compact().to_numpy()["id"]
    assert got.tolist() == want.tolist()


# ---------------------------------------------------------------------------
# cohort-algebra parser
# ---------------------------------------------------------------------------
def test_parser_precedence_and_parens():
    assert parse_cohort_expr("a | b & c") == CohortCombine(
        "|", CohortRef("a"), CohortCombine("&", CohortRef("b"), CohortRef("c")))
    assert parse_cohort_expr("(a | b) - c") == CohortCombine(
        "-", CohortCombine("|", CohortRef("a"), CohortRef("b")), CohortRef("c"))
    # legacy flat expressions keep their left-fold meaning
    assert parse_cohort_expr("a & b - c") == CohortCombine(
        "-", CohortCombine("&", CohortRef("a"), CohortRef("b")), CohortRef("c"))
    assert parse_cohort_expr("a - b - c") == CohortCombine(
        "-", CohortCombine("-", CohortRef("a"), CohortRef("b")), CohortRef("c"))
    # operand names keep non-paren characters (legacy bracketed names)
    assert parse_cohort_expr("( drug_purchases[cip13] )") == \
        CohortRef("drug_purchases[cip13]")


@pytest.mark.parametrize("bad", ["", "a b", "a &", "& a", "(a | b", "a ) b",
                                 "a & ( )", "a | | b"])
def test_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_cohort_expr(bad)


def _algebra_study(flat):
    a = Extractor(name="ea", source="T", category=1, value_col="v",
                  start_col="s", codes=(1, 2, 3))
    b = Extractor(name="eb", source="T", category=1, value_col="v",
                  start_col="s", codes=(2, 3, 4))
    c = Extractor(name="ec", source="T", category=1, value_col="v",
                  start_col="s", codes=(3, 4, 5))
    s = Study(n_patients=32)
    for name, ex in (("a", a), ("b", b), ("c", c)):
        s.extract(ex, name=name)
    return s


@pytest.fixture(scope="module")
def algebra_flat():
    rng = np.random.default_rng(5)
    n = 200
    return _table(patient_id=rng.integers(0, 32, n),
                  v=rng.integers(0, 8, n), s=rng.integers(0, 100, n))


def test_cohort_precedence_semantics(algebra_flat):
    res = (_algebra_study(algebra_flat)
           .cohort("mixed", "a | b & c")
           .cohort("grouped", "(a | b) & c")
           .cohort("ca", "a").cohort("cb", "b").cohort("cc", "c")
           .run({"T": algebra_flat}))
    A, B, C = (res.cohorts[k] for k in ("ca", "cb", "cc"))
    want_mixed = A.union(B.intersection(C))
    want_grouped = A.union(B).intersection(C)
    assert (np.asarray(res.cohorts["mixed"].subjects)
            == np.asarray(want_mixed.subjects)).all()
    assert (np.asarray(res.cohorts["grouped"].subjects)
            == np.asarray(want_grouped.subjects)).all()
    # the two really differ on this data — the old left-fold bug was silent
    assert (np.asarray(want_mixed.subjects)
            != np.asarray(want_grouped.subjects)).any()


def test_cohort_paren_difference(algebra_flat):
    res = (_algebra_study(algebra_flat)
           .cohort("x", "(a | b) - c")
           .cohort("ca", "a").cohort("cb", "b").cohort("cc", "c")
           .run({"T": algebra_flat}))
    want = res.cohorts["ca"].union(res.cohorts["cb"]).difference(
        res.cohorts["cc"])
    assert (np.asarray(res.cohorts["x"].subjects)
            == np.asarray(want.subjects)).all()


def test_legacy_flat_expression_bit_for_bit(algebra_flat):
    """Legacy flat expressions whose old left fold agreed with standard
    precedence (every & before |/-) keep their exact meaning through the new
    parser; mixes like "a | b & c" intentionally change — that silent
    left-fold reading was the bug (covered above)."""
    res = (_algebra_study(algebra_flat)
           .cohort("old", "a & b - c")
           .cohort("ca", "a").cohort("cb", "b").cohort("cc", "c")
           .run({"T": algebra_flat}))
    want = res.cohorts["ca"].intersection(res.cohorts["cb"]).difference(
        res.cohorts["cc"])
    assert (np.asarray(res.cohorts["old"].subjects)
            == np.asarray(want.subjects)).all()


# ---------------------------------------------------------------------------
# join-aware column pruning (the acceptance criterion)
# ---------------------------------------------------------------------------
def _scan_projections(plan):
    """{source: effective projected column set} for every star scan."""
    out = {}
    for i, n in enumerate(plan.nodes):
        if n.op != "scan_star":
            continue
        cols = set(n.get("columns") or ())
        for j in plan.consumers()[i]:
            if plan.nodes[j].op == "select":
                cols = set(plan.nodes[j].get("cols"))
        out[n.get("source")] = cols
    return out


def test_pruning_drops_unreferenced_dimension_columns(dcir):
    s = (Study(n_patients=CFG.n_patients)
         .flatten(DCIR_SCHEMA)
         .extract(drug_dispenses(), name="drugs")
         .extract(medical_acts_dcir(), name="acts"))
    opt = s.optimized_plan()
    proj = _scan_projections(opt)
    # referenced: union extractor projection + join keys; everything else in
    # each star table must be gone before the first join
    assert proj["IR_BEN"] == {"patient_id"}            # pure join key
    assert proj["ER_PHA"] == {"flow_id", "cip13"}      # drops atc, quantity
    assert proj["ER_CAM"] == {"flow_id", "ccam_code"}
    assert proj["ER_PRS"] == {"flow_id", "patient_id", "execution_date"}
    # end-to-end: pruned results identical to the unpruned plan
    res = s.run(dict(dcir))
    unpruned = optimize(s.plan(), tables=dict(dcir), prune_cols=False)
    vals = execute(unpruned, dict(dcir))
    for name in ("drugs", "acts"):
        a = res.events[name].to_numpy()
        b = vals[unpruned.output_ids[name]].to_numpy()
        assert set(a) == set(b)
        for k in a:
            assert (a[k] == b[k]).all(), (name, k)
    # and the pruned plan scans strictly fewer columns
    n_pruned = sum(len(c) for c in proj.values())
    n_full = sum(len(c) for c in _scan_projections(unpruned).values())
    assert n_pruned < n_full


def test_pruning_expand_join_parity(pmsi):
    """1:N star (PMSI): pruning through expand_join keeps results identical
    while narrowing the scans."""
    def build():
        return (Study(n_patients=CFG.n_patients)
                .flatten(PMSI_MCO_SCHEMA, name="PMSI")
                .extract(Extractor(
                    name="hospital_acts", source="PMSI", category=2,
                    value_col="ccam_code", start_col="act_date",
                    null_cols=("ccam_code",),
                    distinct=("stay_id", "ccam_code", "act_date")),
                    name="hacts"))
    pruned = build().run(dict(pmsi))
    pruned.assert_no_loss()
    s2 = build()
    unpruned_plan = optimize(s2.plan(), tables=dict(pmsi), prune_cols=False)
    vals = execute(unpruned_plan, dict(pmsi))
    a = pruned.events["hacts"].to_numpy()
    b = vals[unpruned_plan.output_ids["hacts"]].to_numpy()
    for k in a:
        assert (a[k] == b[k]).all(), k
    proj = _scan_projections(pruned.plan)
    assert "icd_code" not in proj["MCO_D"] or proj["MCO_D"] == {"stay_id"}
    assert proj["MCO_D"] == {"stay_id"}                # unused 1:N child
    assert proj["MCO_A"] == {"stay_id", "ccam_code", "act_date"}


def test_keep_true_pins_full_flat_schema(dcir):
    s = (Study(n_patients=CFG.n_patients)
         .flatten(DCIR_SCHEMA, keep=True)
         .extract(drug_dispenses(), name="drugs"))
    opt = s.optimized_plan()
    proj = _scan_projections(opt)
    # the materialized flat output demands every star column: no pruning
    assert proj["IR_BEN"] == {"patient_id", "gender", "birth_date",
                              "death_date"}
    res = s.run(dict(dcir))
    assert "DCIR" in res.events
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    assert set(res.events["DCIR"].column_names) == set(flat.column_names)


def test_auto_demote_only_when_chained(dcir):
    kept = Study(n_patients=CFG.n_patients).flatten(DCIR_SCHEMA)
    assert "DCIR" in dict(kept.plan().outputs)        # nothing chained: kept
    chained = (Study(n_patients=CFG.n_patients)
               .flatten(DCIR_SCHEMA)
               .extract(drug_dispenses(), name="drugs"))
    assert "DCIR" not in dict(chained.plan().outputs)
    res = chained.run(dict(dcir))
    assert "DCIR" not in res.events and "drugs" in res.events


def test_pruned_study_sharded_matches_local(dcir):
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    build = lambda: (Study(n_patients=CFG.n_patients)
                     .flatten(DCIR_SCHEMA)
                     .extract(drug_dispenses(), name="drugs"))
    local = build().run(dict(dcir))
    sharded = build().run(dict(dcir), mesh=mesh)
    a, b = local.events["drugs"].to_numpy(), sharded.events["drugs"].to_numpy()
    for k in a:
        assert (a[k] == b[k]).all(), k


def test_column_audit_recorded_in_log(dcir):
    res = (Study(n_patients=CFG.n_patients)
           .flatten(DCIR_SCHEMA)
           .extract(drug_dispenses(), name="drugs")
           .run(dict(dcir)))
    rows = column_audit_from_log(res.log)
    assert rows                                        # audit rows exist
    by_stage = {r["stage"]: r for r in rows}
    pruned = [r for r in rows if r.get("pruned_columns")]
    dropped = {c for r in pruned for c in r["pruned_columns"]}
    assert {"gender", "birth_date", "death_date"} <= dropped
    join_rows = [r for r in by_stage if "lookup_join" in r]
    assert join_rows and all(by_stage[r]["required_columns"]
                             for r in join_rows)
