"""Differential harness for the whole predicate stack: THREE independent
evaluation paths of the same ``Expr`` must agree bit-for-bit on every table —

  1. **naive**: per-node ``Expr.evaluate`` chained one predicate at a time
     (the reference semantics, ``expr.py``);
  2. **fused jnp**: the optimizer fuses the predicate chain into one
     ``fused_mask`` node executed as a single jnp conjunction;
  3. **pallas**: the same fused node stamped ``engine="pallas"`` and executed
     through the Expr->bitset kernel (interpret mode off-TPU), including the
     packed-word round-trip (``Bitset.from_mask``/``to_mask``).

Hypothesis generates random Expr trees over random ColumnarTables (mixed
int32/float32 dtypes, NULL sentinels, NaNs, random validity, ragged
non-block-multiple lengths); a deterministic battery pins the same
coverage on fixed cases.
"""
import numpy as np
import pytest
import hypothesis.strategies as st
from hypothesis import given, settings

import jax.numpy as jnp

from repro.core.cohort import Bitset
from repro.core.columnar import ColumnarTable, NULL_INT
from repro.kernels.predicate import compilable, predicate_bitset
from repro.study import PlanBuilder, assign_engines, col, execute, optimize
from repro.study.expr import all_of

BLOCK = 64   # small block -> multi-block grids even on tiny tables


def _table(valid=None, **cols) -> ColumnarTable:
    arrs = {}
    for k, v in cols.items():
        a = np.asarray(v)
        arrs[k] = a.astype(np.float32 if a.dtype.kind == "f" else np.int32)
    v = None if valid is None else jnp.asarray(np.asarray(valid, bool))
    return ColumnarTable.from_columns(arrs, valid=v)


def _rand_table(rng, n: int) -> ColumnarTable:
    a = rng.integers(-5, 15, n)
    a[rng.random(n) < 0.25] = int(NULL_INT)
    x = rng.normal(size=n).astype(np.float32)
    x[rng.random(n) < 0.2] = np.nan
    return _table(valid=rng.random(n) < 0.85, id=np.arange(n),
                  a=a, b=rng.integers(-5, 15, n), x=x)


# ---------------------------------------------------------------------------
# the three paths
# ---------------------------------------------------------------------------
def _naive_ids(t: ColumnarTable, exprs) -> list:
    """Reference: chain per-node evaluation, one predicate at a time."""
    cur = t
    for e in exprs:
        cur = cur.filter(e.mask(cur))
    return np.asarray(cur.columns["id"])[cur.valid_numpy()].tolist()


def _engine_ids(t: ColumnarTable, exprs, engine: str) -> list:
    """Build predicate-chain plan, fuse to ONE fused_mask, stamp ``engine``,
    execute, return the surviving row ids (in order — the optimizer appends
    one compaction to the named output, identical for both engines)."""
    b = PlanBuilder()
    nid = b.scan("T")
    for e in exprs:
        nid = b.predicate(nid, e)
    b.set_output("out", nid)
    opt = optimize(b.build(), predicate_engine="jnp")
    assert opt.count_ops().get("fused_mask", 0) == 1
    opt = assign_engines(opt, predicate_engine=engine, block=BLOCK)
    out = execute(opt, {"T": t})[opt.output_ids["out"]]
    return out.to_numpy()["id"].tolist()


def _assert_three_way(t: ColumnarTable, exprs) -> None:
    want = _naive_ids(t, exprs)
    got_jnp = _engine_ids(t, exprs, "jnp")
    got_pal = _engine_ids(t, exprs, "pallas")
    assert got_jnp == want, "fused jnp != naive"
    assert got_pal == want, "pallas kernel != naive"

    # kernel-level + packed-word round-trips on the fused conjunction
    fused = all_of(*exprs)
    param = fused.to_param()
    if compilable(param):
        n = t.capacity
        want_mask = np.asarray(fused.mask(t))
        words, cnt = predicate_bitset(t.columns, t.valid, expr_param=param,
                                      block=BLOCK, interpret=True)
        assert int(cnt) == int(want_mask.sum())
        unpacked = np.asarray(Bitset.to_mask(words, n))
        assert unpacked.tolist() == want_mask.tolist(), "bitset unpack"
        repacked = np.asarray(Bitset.from_mask(jnp.asarray(want_mask)))
        assert np.array_equal(repacked, np.asarray(words)), "bitset repack"


# ---------------------------------------------------------------------------
# deterministic battery (runs without hypothesis)
# ---------------------------------------------------------------------------
CASES = [
    # each leaf op; ragged + block-boundary lengths; NULL/NaN interplay
    ("cmp_int", 63, lambda: [col("a") >= 3]),
    ("cmp_chain", 64, lambda: [col("a") >= 3, col("b") < 10]),
    ("isin", 65, lambda: [col("a").isin([1, 2, 9])]),
    ("isin_empty", 40, lambda: [col("a").isin([])]),
    ("isin_float_probe", 100, lambda: [col("x").isin([0, 1])]),
    ("null_tests", 130, lambda: [col("a").not_null(), col("x").not_null()]),
    ("arith", 129, lambda: [(col("a") + 2) % 3 == 1, col("b") * 2 >= col("a")]),
    ("float_cmp", 128, lambda: [col("x") > 0.25, ~(col("x") <= 0.75)]),
    ("bool_mix", 200, lambda: [(col("a").is_null() | (col("a") > 4))
                               & (col("b") != 7)]),
    ("between", 47, lambda: [col("b").between(-1, 9)]),
    ("deep", 333, lambda: [~((col("a") < 0) | col("x").is_null())
                           & (col("a").isin([3, 4, 5]) | (col("b") % 2 == 0))]),
]


@pytest.mark.parametrize("name,n,mk", CASES, ids=[c[0] for c in CASES])
def test_three_way_battery(name, n, mk):
    rng = np.random.default_rng(hash(name) % 2**31)
    _assert_three_way(_rand_table(rng, n), mk())


def test_three_way_single_row_and_all_invalid():
    rng = np.random.default_rng(7)
    _assert_three_way(_rand_table(rng, 1), [col("a") >= 0])
    t = _table(valid=np.zeros(50, bool), id=np.arange(50), a=np.arange(50),
               b=np.arange(50), x=np.arange(50).astype(np.float32))
    _assert_three_way(t, [col("a") >= 0])


def test_kernel_empty_table():
    words, cnt = predicate_bitset({"a": jnp.zeros((0,), jnp.int32)},
                                  jnp.zeros((0,), bool),
                                  expr_param=(col("a") >= 0).to_param(),
                                  block=BLOCK, interpret=True)
    assert words.shape == (0,) and int(cnt) == 0


def test_oversized_isin_falls_back_to_jnp():
    """Whitelists past the membership budget are not kernel-compilable;
    assign_engines stamps them back to jnp and execution still agrees."""
    from repro.kernels.predicate import MAX_ISIN_VALUES

    big = col("a").isin(range(MAX_ISIN_VALUES + 1))
    small = col("a").isin(range(8))
    assert not compilable(big.to_param())
    assert compilable(small.to_param())

    rng = np.random.default_rng(3)
    t = _rand_table(rng, 100)
    b = PlanBuilder()
    b.set_output("out", b.predicate(b.scan("T"), big))
    opt = assign_engines(optimize(b.build()), predicate_engine="pallas",
                         block=BLOCK)
    masks = [n for n in opt.nodes if n.op == "fused_mask"]
    assert masks and all(n.get("engine") == "jnp" for n in masks)
    got = execute(opt, {"T": t})[opt.output_ids["out"]].to_numpy()["id"]
    assert got.tolist() == _naive_ids(t, [big])


def test_kernel_rejects_non_boolean_root():
    with pytest.raises(ValueError):
        predicate_bitset({"a": jnp.zeros((4,), jnp.int32)},
                         jnp.ones((4,), bool),
                         expr_param=(col("a") + 1).to_param(),
                         block=BLOCK, interpret=True)
    assert not compilable((col("a") + 1).to_param())
    assert compilable((col("a") >= 1).to_param())


def test_engine_pallas_routes_predicates_through_kernel():
    """Acceptance: under the global ``engine="pallas"`` the optimizer stamps
    every fused_mask with the bitset kernel engine (auto resolves through the
    global engine even off-TPU), and execution stays bit-identical."""
    rng = np.random.default_rng(21)
    t = _rand_table(rng, 150)
    b = PlanBuilder()
    nid = b.predicate(b.predicate(b.scan("T"), col("a") >= 2),
                      col("b") < 9)
    b.set_output("out", nid)
    opt = optimize(b.build(), predicate_engine="auto", engine="pallas")
    masks = [n for n in opt.nodes if n.op == "fused_mask"]
    assert masks and all(n.get("engine") == "pallas" for n in masks)
    assert all(n.get("bitset_word") == "uint32" for n in masks)
    got = execute(opt, {"T": t}, engine="xla")[opt.output_ids["out"]]
    want = _naive_ids(t, [col("a") >= 2, col("b") < 9])
    assert got.to_numpy()["id"].tolist() == want


# ---------------------------------------------------------------------------
# hypothesis: random Expr trees x random tables
# ---------------------------------------------------------------------------
_COLS = ("a", "b", "x")


def _random_pred(draw, depth: int):
    c = col(_COLS[draw(st.integers(0, 2))])
    if depth <= 0 or draw(st.integers(0, 2)) == 0:
        kind = draw(st.integers(0, 4))
        if kind == 0:
            op = draw(st.sampled_from(["==", "!=", "<", "<=", ">", ">="]))
            rhs = (draw(st.integers(-5, 15)) if draw(st.booleans())
                   else draw(st.floats(-2, 2, allow_nan=False, width=32)))
            return {"==": c.__eq__, "!=": c.__ne__, "<": c.__lt__,
                    "<=": c.__le__, ">": c.__gt__, ">=": c.__ge__}[op](rhs)
        if kind == 1:
            vals = draw(st.lists(st.integers(-5, 15), max_size=6))
            return c.isin(vals)
        if kind == 2:
            return c.is_null() if draw(st.booleans()) else c.not_null()
        if kind == 3:
            lo = draw(st.integers(-5, 5))
            return c.between(lo, lo + draw(st.integers(0, 10)))
        # nonzero literal divisor: int division by zero is backend-defined
        return (c + draw(st.integers(0, 3))) % draw(st.integers(1, 4)) \
            == draw(st.integers(0, 3))
    k = draw(st.integers(0, 2))
    l = _random_pred(draw, depth - 1)
    if k == 0:
        return ~l
    r = _random_pred(draw, depth - 1)
    return (l & r) if k == 1 else (l | r)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_property_three_way_parity(data):
    """naive per-node == fused jnp conjunction == pallas bitset kernel, on
    random trees over random tables (mixed dtypes, sentinels, ragged n)."""
    draw = data.draw
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n = draw(st.integers(1, 3 * BLOCK + 5))
    exprs = [_random_pred(draw, draw(st.integers(0, 2)))
             for _ in range(draw(st.integers(1, 3)))]
    _assert_three_way(_rand_table(rng, n), exprs)


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_property_bitset_roundtrip(data):
    """Packing is lossless at every length: from_mask ∘ to_mask == id on the
    kernel's words, and popcounts equal mask sums."""
    draw = data.draw
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    n = draw(st.integers(1, 200))
    t = _rand_table(rng, n)
    e = _random_pred(draw, 1)
    param = e.to_param()
    words, cnt = predicate_bitset(t.columns, t.valid, expr_param=param,
                                  block=BLOCK, interpret=True)
    mask = np.asarray(Bitset.to_mask(words, n))
    assert int(cnt) == int(mask.sum())
    assert np.array_equal(np.asarray(Bitset.from_mask(jnp.asarray(mask))),
                          np.asarray(words))
    assert mask.tolist() == np.asarray(e.mask(t)).tolist()
