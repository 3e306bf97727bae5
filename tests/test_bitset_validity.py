"""Bitset-native validity: parity, layout and no-unpack guarantees.

The table/cohort data model carries row validity as a packed uint32 bitset
(``core.bitset`` layout) end-to-end.  This module pins the redesign:

  * ``from_columns`` accepts bool-valid and bitset-valid forms, validates
    their length, and both produce bit-identical tables (property test +
    deterministic battery over every columnar op);
  * every *plan* op (mask, compact, join, slice_time, flow, stats battery)
    is bit-identical under bool-valid vs bitset-valid input tables, locally
    and under ``jax.shard_map``;
  * the optimizer's ``eliminate_joins`` degrades a pruned-to-key lookup_join
    to an audit-only ``key_count`` without changing results;
  * executor-level no-unpack assertion: on the Pallas engines the
    predicate -> cohort -> compaction path never expands validity back to a
    bool column (``bitset.unpack`` is instrumented and must not fire);
  * the ">25 statistics" battery expands each cohort/table bitset ONCE per
    ``stats.compute`` (memoized unpack).
"""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.bitset as bitset
from repro.core.bitset import pack, unpack_np
from repro.core.cohort import Bitset, Cohort
from repro.core.columnar import ColumnarTable, NULL_INT
from repro.core import stats
from repro.data.synthetic import SyntheticConfig, generate_dcir
from repro.study import Study, col, execute
from repro.study.optimizer import eliminate_joins, optimize, prune_columns
from repro.study.plan import PlanBuilder


def _mk(vals, valid=None, extra=None):
    cols = {"a": np.asarray(vals, np.int32),
            "b": np.asarray(vals, np.int32) * 3}
    if extra:
        cols.update(extra)
    return ColumnarTable.from_columns(
        cols, valid=None if valid is None else valid)


def _same(t1: ColumnarTable, t2: ColumnarTable):
    assert t1.capacity == t2.capacity
    assert int(t1.count) == int(t2.count)
    assert np.array_equal(np.asarray(t1.valid), np.asarray(t2.valid))
    assert t1.column_names == t2.column_names
    a, b = t1.to_numpy(), t2.to_numpy()
    for k in a:
        assert np.array_equal(a[k], b[k]), k


# ---------------------------------------------------------------------------
# from_columns compatibility surface + validation (satellite: length checks)
# ---------------------------------------------------------------------------
def test_from_columns_accepts_bool_and_bitset():
    mask = np.asarray([True, False, True, True, False], bool)
    t_bool = _mk(range(5), valid=mask)
    t_bits = _mk(range(5), valid=pack(jnp.asarray(mask)))
    assert t_bool.valid.dtype == jnp.uint32 and t_bits.valid.dtype == jnp.uint32
    _same(t_bool, t_bits)


def test_from_columns_validates_bool_mask_length():
    with pytest.raises(ValueError, match="valid mask length"):
        _mk(range(5), valid=np.ones(4, bool))


def test_from_columns_validates_packed_word_length():
    # 5 rows need 1 word; handing 2 words must fail loudly, not corrupt count
    with pytest.raises(ValueError, match="packed valid"):
        _mk(range(5), valid=jnp.ones((2,), jnp.uint32))


def test_from_columns_clears_packed_tail_bits():
    # caller-supplied words with garbage past the capacity: count stays exact
    words = jnp.asarray([0xFFFFFFFF], jnp.uint32)
    t = _mk(range(5), valid=words)
    assert int(t.count) == 5
    assert int(np.asarray(t.valid)[0]) == 0b11111


def test_valid_bool_roundtrip():
    mask = np.asarray([True, False] * 17, bool)          # ragged (34 rows)
    t = _mk(range(34), valid=mask)
    assert np.array_equal(np.asarray(t.valid_bool()), mask)
    assert np.array_equal(t.valid_numpy(), mask)


# ---------------------------------------------------------------------------
# columnar-op parity: bool-valid vs bitset-valid tables
# ---------------------------------------------------------------------------
def _op_battery(t: ColumnarTable, mask2: np.ndarray):
    yield t.filter(jnp.asarray(mask2))
    yield t.filter(pack(jnp.asarray(mask2)))             # packed filter mask
    yield t.drop_nulls(["a"])
    yield t.compact()
    yield t.sort_by(["a"])
    yield t.take(jnp.arange(t.capacity)[::-1])
    yield t.pad_to(t.capacity + 7)
    yield t.shrink_to(max(t.capacity - 3, 1))
    yield ColumnarTable.concat([t, t])
    yield t.select(["a"])


def _run_battery(vals, mask, mask2):
    vals = np.asarray(vals, np.int32)
    mask = np.asarray(mask, bool)
    t_bool = _mk(vals, valid=mask)
    t_bits = _mk(vals, valid=pack(jnp.asarray(mask)))
    for o1, o2 in zip(_op_battery(t_bool, mask2), _op_battery(t_bits, mask2)):
        _same(o1, o2)
    m1 = t_bool.monitoring_stats("a")
    m2 = t_bits.monitoring_stats("a")
    for k in m1:
        assert int(m1[k]) == int(m2[k]), k


def test_op_battery_deterministic():
    rng = np.random.RandomState(7)
    for n in (1, 5, 31, 32, 33, 64, 100):
        vals = rng.randint(-50, 50, size=n)
        vals[rng.rand(n) < 0.2] = int(NULL_INT)
        _run_battery(vals, rng.rand(n) < 0.6, rng.rand(n) < 0.5)


@settings(max_examples=40, deadline=None)
@given(vals=st.lists(st.integers(-100, 100), min_size=1, max_size=80),
       data=st.data())
def test_op_battery_property(vals, data):
    n = len(vals)
    mask = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    mask2 = data.draw(st.lists(st.booleans(), min_size=n, max_size=n))
    _run_battery(vals, mask, mask2)


# ---------------------------------------------------------------------------
# plan-op parity: a full study (mask, compact, join, slice_time, flow,
# stats battery) under bool-valid vs bitset-valid env tables, local + sharded
# ---------------------------------------------------------------------------
CFG = SyntheticConfig(n_patients=120, seed=11)


@pytest.fixture(scope="module")
def dcir():
    return generate_dcir(CFG)


def _retype_valid(tables, form: str):
    out = {}
    for k, t in tables.items():
        v = t.valid_bool() if form == "bool" else t.valid
        out[k] = ColumnarTable.from_columns(dict(t.columns), valid=v)
    return out


def _study():
    from repro.core import DCIR_SCHEMA, drug_dispenses, medical_acts_dcir

    return (Study(n_patients=CFG.n_patients)
            .flatten(DCIR_SCHEMA, time_slices=2,
                     time_column="execution_date", t0=14_000, t1=16_000)
            .extract(drug_dispenses(), name="drugs")
            .extract(medical_acts_dcir()
                     .filtered(col("execution_date") >= 14_000), name="acts")
            .patients("IR_BEN")
            .cohort("base", "extract_patients")
            .cohort("drugged", "drugs")
            .cohort("final", "drugged & base - acts")
            .flow("base", "drugged", "final"))


def _assert_results_equal(r1, r2):
    assert set(r1.events) == set(r2.events)
    for k in r1.events:
        a, b = r1.events[k].to_numpy(), r2.events[k].to_numpy()
        for c in a:
            assert np.array_equal(a[c], b[c]), (k, c)
    for k in r1.cohorts:
        assert np.array_equal(np.asarray(r1.cohorts[k].subjects),
                              np.asarray(r2.cohorts[k].subjects)), k
    assert [row["subjects"] for row in r1.flow.flowchart()] == \
           [row["subjects"] for row in r2.flow.flowchart()]


@pytest.mark.parametrize("mesh_mode", ["local", "shard_map"])
def test_plan_parity_bool_vs_bitset_valid(dcir, mesh_mode):
    mesh = None
    if mesh_mode == "shard_map":
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    r_bool = _study().run(_retype_valid(dict(dcir), "bool"), mesh=mesh)
    r_bits = _study().run(_retype_valid(dict(dcir), "bits"), mesh=mesh)
    r_bool.assert_no_loss()
    _assert_results_equal(r_bool, r_bits)
    # the stats battery on top must agree too (memoized masks included)
    pats = r_bool.events["extract_patients"]
    s1 = stats.compute(r_bool.cohorts["final"], pats)
    s2 = stats.compute(r_bits.cohorts["final"],
                       r_bits.events["extract_patients"])
    assert s1 == s2


# ---------------------------------------------------------------------------
# eliminate_joins: pruned N:1 join -> audit-only key_count, same results
# ---------------------------------------------------------------------------
def test_eliminate_joins_key_count_audit():
    left = ColumnarTable.from_columns({
        "flow_id": np.asarray([1, 2, 3, 4, int(NULL_INT)], np.int32),
        "patient_id": np.asarray([0, 1, 2, 3, 4], np.int32),
        "val": np.asarray([10, 20, 30, 40, 50], np.int32),
        "execution_date": np.asarray([5, 6, 7, 8, 9], np.int32),
    })
    right = ColumnarTable.from_columns({
        "flow_id": np.asarray([2, 4, 9], np.int32),
        "extra": np.asarray([7, 8, 9], np.int32),
    })

    def build():
        b = PlanBuilder()
        l = b.scan_star("L", columns=("flow_id", "patient_id", "val",
                                      "execution_date"))
        r = b.scan_star("R", columns=("flow_id", "extra"))
        j = b.lookup_join(l, r, "flow_id", "flow_id")
        p = b.predicate(j, col("val") >= 20)
        e = b.conform_events(p, name="ev", category=2, value_col="val",
                             start_col="execution_date")
        b.set_output("ev", b.compact(e))
        return b.build()

    raw = build()
    opt = optimize(raw)
    ops = opt.count_ops()
    assert ops.get("lookup_join", 0) == 0 and ops.get("key_count", 0) == 1

    env = {"L": left, "R": right}
    sink = {}
    v_raw = execute(raw, env, jit=False)
    v_opt = execute(opt, env, stats_sink=sink)
    a = v_raw[raw.output_ids["ev"]].to_numpy()
    b_ = v_opt[opt.output_ids["ev"]].to_numpy()
    for k in a:
        assert np.array_equal(a[k], b_[k]), k
    (kc_stats,) = [d for i, d in sink.items()
                   if opt.nodes[i].op == "key_count"]
    # membership audit: keys 2 and 4 hit; the NULL left key is counted
    assert kc_stats["matched"] == 2
    assert kc_stats["null_keys"] == 1
    assert kc_stats["rows_in"] == kc_stats["rows_out"] == 5
    assert kc_stats["overflow"] == 0


def test_key_count_empty_right_table():
    # lookup_join guards cap_r == 0; its key_count remnant must too
    left = ColumnarTable.from_columns({
        "flow_id": np.asarray([1, 2], np.int32),
        "patient_id": np.asarray([0, 1], np.int32),
        "val": np.asarray([10, 20], np.int32),
        "d": np.asarray([5, 6], np.int32),
    })
    right = ColumnarTable.empty({"flow_id": np.int32, "extra": np.int32}, 0)
    b = PlanBuilder()
    l = b.scan_star("L", columns=("flow_id", "patient_id", "val", "d"))
    r = b.scan_star("R", columns=("flow_id", "extra"))
    j = b.lookup_join(l, r, "flow_id", "flow_id")
    p = b.predicate(j, col("val") >= 0)
    # conform is the schema boundary that un-pins the output's full schema,
    # letting required_columns prove the right side contributes nothing
    e = b.conform_events(p, name="ev", category=1, value_col="val",
                         start_col="d")
    b.set_output("out", b.compact(e))
    opt = optimize(b.build())
    assert opt.count_ops().get("key_count", 0) == 1
    sink = {}
    vals = execute(opt, {"L": left, "R": right}, stats_sink=sink)
    assert int(vals[opt.output_ids["out"]].count) == 2
    (kc,) = [d for i, d in sink.items() if opt.nodes[i].op == "key_count"]
    assert kc["matched"] == 0 and kc["rows_out"] == 2


def test_eliminate_joins_keeps_needed_joins():
    # if a consumer reads a right-side column the join must survive
    b = PlanBuilder()
    l = b.scan_star("L", columns=("flow_id", "val"))
    r = b.scan_star("R", columns=("flow_id", "extra"))
    j = b.lookup_join(l, r, "flow_id", "flow_id")
    p = b.predicate(j, col("extra") >= 0)
    b.set_output("out", b.compact(p))
    opt = eliminate_joins(prune_columns(b.build()))
    assert opt.count_ops().get("lookup_join", 0) == 1


# ---------------------------------------------------------------------------
# executor-level no-unpack assertion on the pallas predicate->cohort->compact
# path (the acceptance criterion of the bitset-native redesign)
# ---------------------------------------------------------------------------
class _UnpackCounter:
    def __init__(self, monkeypatch):
        self.calls = 0
        orig = bitset.unpack

        def counting(words, n_bits):
            self.calls += 1
            return orig(words, n_bits)

        monkeypatch.setattr(bitset, "unpack", counting)


def _hot_path_plan():
    b = PlanBuilder()
    t = b.scan("EV")
    m = b.predicate(t, (col("value") >= 3) & col("value").not_null())
    c1 = b.cohort_from_events(m, name="hi")
    m2 = b.predicate(t, col("start") < 50)
    c2 = b.cohort_from_events(m2, name="early")
    both = b.cohort_op("&", c1, c2, name="both")
    b.set_output("both", both)
    b.set_output("hi_events", b.compact(m))
    return b.build()


def test_pallas_path_never_unpacks(monkeypatch):
    rng = np.random.RandomState(3)
    ev = ColumnarTable.from_columns({
        "patient_id": rng.randint(0, 40, 200).astype(np.int32),
        "value": rng.randint(0, 9, 200).astype(np.int32),
        "start": rng.randint(0, 100, 200).astype(np.int32),
    }, valid=rng.rand(200) < 0.8)
    plan = _hot_path_plan()
    ctr = _UnpackCounter(monkeypatch)
    vals = execute(plan, {"EV": ev}, n_patients=40, engine="pallas",
                   predicate_engine="pallas", jit=False)
    assert ctr.calls == 0, (
        f"pallas predicate->cohort->compaction path expanded validity to a "
        f"bool column {ctr.calls} time(s)")
    # layout check: every exported table carries packed uint32 validity
    out = vals[plan.output_ids["hi_events"]]
    assert out.valid.dtype == jnp.uint32
    assert out.valid.shape[0] == -(-out.capacity // 32)
    # sanity: the instrumentation does fire on the jnp fallback path
    ctr2 = _UnpackCounter(monkeypatch)
    execute(plan, {"EV": ev}, n_patients=40, engine="xla",
            predicate_engine="jnp", jit=False)
    assert ctr2.calls > 0


def test_pallas_and_jnp_engines_bit_identical(dcir):
    r_j = _study().run(dict(dcir), predicate_engine="jnp")
    r_p = _study().run(dict(dcir), predicate_engine="pallas")
    _assert_results_equal(r_j, r_p)


# ---------------------------------------------------------------------------
# stats: one bitset expansion per compute() battery (memoization satellite)
# ---------------------------------------------------------------------------
_PATIENT_STATS = ["gender_distribution", "mortality", "age_buckets",
                  "age_mean", "mortality_rate", "gender_ratio"]


def test_stats_unpack_memoized(monkeypatch):
    rng = np.random.RandomState(5)
    n = 64
    patients = ColumnarTable.from_columns({
        "patient_id": np.arange(n, dtype=np.int32),
        "gender": rng.randint(1, 3, n).astype(np.int32),
        "birth_date": rng.randint(0, 10_000, n).astype(np.int32),
        "death_date": np.full(n, int(NULL_INT), np.int32),
    })
    cohort = Cohort(name="c", description="c",
                    subjects=pack(jnp.asarray(rng.rand(n) < 0.5)),
                    n_patients=n)
    ctr = _UnpackCounter(monkeypatch)
    out = stats.compute(cohort, patients, names=list(_PATIENT_STATS))
    assert set(out) == set(_PATIENT_STATS)
    # exactly two expansions: the subject bitset + the patients validity;
    # all six statistics share them through the memoized masks
    assert ctr.calls == 2, ctr.calls
    stats.compute(cohort, patients, names=list(_PATIENT_STATS))
    assert ctr.calls == 2  # second battery: fully cached


def test_subjects_mask_memoized():
    n = 50
    c = Cohort(name="c", description="c",
               subjects=pack(jnp.ones((n,), bool)), n_patients=n)
    m1 = c.subjects_mask()
    assert c.subjects_mask() is m1


# ---------------------------------------------------------------------------
# sort/dedupe stay word-wise (satellite of the cohort-service PR): sorting
# gathers bits straight from the packed words and re-emits first_n words;
# dedupe's row validity is an iota compare on the sorted table
# ---------------------------------------------------------------------------
def test_sort_and_dedupe_never_unpack(monkeypatch):
    from repro.core.extraction import dedupe_by

    rng = np.random.RandomState(5)
    t = _mk(rng.randint(0, 7, 97), valid=rng.rand(97) < 0.7,
            extra={"k": rng.randint(0, 5, 97).astype(np.int32)})
    ctr = _UnpackCounter(monkeypatch)
    s = t.sort_by(["k", "a"])
    d = dedupe_by(t, ["k", "a"])
    jax.block_until_ready((s.valid, d.valid))
    assert ctr.calls == 0, (
        f"sort/dedupe expanded packed validity {ctr.calls} time(s)")
    # layout: packed words out; the sort's valid rows are exactly the first
    # `count` (dedupe keeps a masked table — run heads — by design)
    assert s.valid.dtype == jnp.uint32 and d.valid.dtype == jnp.uint32
    assert np.array_equal(np.asarray(s.valid),
                          np.asarray(bitset.first_n(s.count, s.capacity)))
    # semantics vs a plain numpy reference
    mask = unpack_np(np.asarray(t.valid), t.capacity)
    ks, as_ = np.asarray(t.columns["k"])[mask], np.asarray(t.columns["a"])[mask]
    order = np.lexsort((as_, ks))
    assert np.array_equal(np.asarray(s.columns["k"])[:int(s.count)], ks[order])
    assert np.array_equal(np.asarray(s.columns["a"])[:int(s.count)], as_[order])
    dmask = unpack_np(np.asarray(d.valid), d.capacity)
    got = set(zip(np.asarray(d.columns["k"])[dmask].tolist(),
                  np.asarray(d.columns["a"])[dmask].tolist()))
    assert got == set(zip(ks.tolist(), as_.tolist()))
    assert int(d.count) == len(got)


def test_join_fills_never_unpack(monkeypatch):
    # joins stay word-wise too (satellite of the static-analysis PR): the
    # key fills and the found-mask gather read bits via ``bitset.bit_at``,
    # never expanding validity to a bool column
    from repro.core.flattening import expand_join, lookup_join

    rng = np.random.RandomState(11)
    left = ColumnarTable.from_columns({
        "pid": jnp.asarray(rng.randint(0, 20, 97).astype(np.int32)),
        "v": jnp.asarray(rng.randint(0, 9, 97).astype(np.int32)),
    }, valid=jnp.asarray(rng.rand(97) < 0.8))
    right = ColumnarTable.from_columns({
        "pid": jnp.asarray(np.arange(20, dtype=np.int32)),
        "w": jnp.asarray(rng.randint(0, 5, 20).astype(np.int32)),
    }, valid=jnp.asarray(rng.rand(20) < 0.9))
    child = ColumnarTable.from_columns({
        "pid": jnp.asarray(rng.randint(0, 20, 64).astype(np.int32)),
        "x": jnp.asarray(rng.randint(0, 5, 64).astype(np.int32)),
    }, valid=jnp.asarray(rng.rand(64) < 0.9))
    ctr = _UnpackCounter(monkeypatch)
    j, _ = lookup_join(left, right, "pid", "pid", prefix="r_")
    e, _ = expand_join(left, child, "pid", "pid", 512, prefix="c_")
    jax.block_until_ready((j.valid, e.valid))
    assert ctr.calls == 0, (
        f"join key fills expanded packed validity {ctr.calls} time(s)")
    # layout: packed uint32 words out of both join flavours
    assert j.valid.dtype == jnp.uint32 and e.valid.dtype == jnp.uint32
    assert j.valid.shape[0] == -(-j.capacity // 32)
    assert e.valid.shape[0] == -(-e.capacity // 32)
    # semantics vs a numpy reference: every valid left row survives the
    # lookup join, and its right attribute is the match or the null sentinel
    lmask = unpack_np(np.asarray(left.valid), left.capacity)
    rmask = unpack_np(np.asarray(right.valid), right.capacity)
    jmask = unpack_np(np.asarray(j.valid), j.capacity)
    assert np.array_equal(jmask, lmask)
    rmap = {int(k): int(w) for k, w, ok in zip(
        np.asarray(right.columns["pid"]), np.asarray(right.columns["w"]),
        rmask) if ok}
    for i in np.nonzero(lmask)[0]:
        k = int(np.asarray(left.columns["pid"])[i])
        want = rmap.get(k, NULL_INT)
        assert int(np.asarray(j.columns["r_w"])[i]) == want
    # expand join: one output row per (valid left, valid child) key pair,
    # plus one null-filled row per unmatched valid left row
    cmask = unpack_np(np.asarray(child.valid), child.capacity)
    ckeys = np.asarray(child.columns["pid"])[cmask]
    n_pairs = sum(
        max(int((ckeys == int(np.asarray(left.columns["pid"])[i])).sum()), 1)
        for i in np.nonzero(lmask)[0])
    assert int(e.count) == n_pairs
