"""Cohort algebra tests: bitset <-> set homomorphism (hypothesis), flow
flowcharts, description composition (paper Supplementary Out[6])."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Bitset, Category, Cohort, CohortCollection, CohortFlow, make_events


def cohort_from_set(name, s, n):
    idx = jnp.asarray(sorted(s) or [0], jnp.int32)
    valid = jnp.asarray([True] * len(s) + ([False] if not s else []))[: max(len(s), 1)]
    bits = Bitset.from_indices(idx, valid, n)
    return Cohort(name=name, description=name, subjects=bits, n_patients=n)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 200),
    data=st.data(),
)
def test_property_bitset_set_homomorphism(n, data):
    a = set(data.draw(st.lists(st.integers(0, n - 1), max_size=n)))
    b = set(data.draw(st.lists(st.integers(0, n - 1), max_size=n)))
    ca = cohort_from_set("a", a, n)
    cb = cohort_from_set("b", b, n)
    assert ca.subject_count() == len(a)
    assert ca.intersection(cb).subject_count() == len(a & b)
    assert ca.union(cb).subject_count() == len(a | b)
    assert ca.difference(cb).subject_count() == len(a - b)
    # mask round-trip
    mask = np.asarray(ca.subjects_mask())
    assert set(np.nonzero(mask)[0].tolist()) == a


def test_descriptions_compose():
    n = 16
    base = cohort_from_set("extract_patients", {0, 1, 2, 3}, n)
    expo = cohort_from_set("exposures", {1, 2, 3, 4}, n)
    frac = cohort_from_set("fractures", {2}, n)
    final = expo.intersection(base).difference(frac)
    assert "without" in final.describe()
    assert final.subject_count() == 2  # {1,3}


def test_cohort_events_filtered_on_combine():
    n = 8
    ev = make_events(
        patient_id=jnp.asarray([0, 1, 2], jnp.int32), category=Category.EXPOSURE,
        value=jnp.asarray([1, 1, 1], jnp.int32),
        start=jnp.asarray([0, 0, 0], jnp.int32),
    )
    ca = Cohort.from_events("a", ev, n)
    cb = cohort_from_set("b", {0, 2}, n)
    inter = ca.intersection(cb)
    assert inter.subject_count() == 2
    kept = inter.events_of()
    assert int(kept.count) == 2


def test_cohort_flow_monotone_and_flowchart():
    n = 32
    c1 = cohort_from_set("s1", set(range(20)), n)
    c2 = cohort_from_set("s2", set(range(5, 32)), n)
    c3 = cohort_from_set("s3", set(range(0, 32, 2)), n)
    flow = CohortFlow([c1, c2, c3])
    counts = [r["subjects"] for r in flow.flowchart()]
    assert counts == sorted(counts, reverse=True)  # fold(∩) can only shrink
    assert flow.flowchart()[1]["removed"] == counts[0] - counts[1]
    assert flow.final.subject_count() == counts[-1]
    assert "stage" in flow.render()


def test_cohort_collection():
    n = 8
    cc = CohortCollection({})
    cc.add(cohort_from_set("x", {1, 2}, n))
    assert cc.cohorts_names == {"x"}
    assert cc.get("x").subject_count() == 2


def test_bitset_kernel_parity():
    """Cohort algebra kernel (Pallas) agrees with the jnp path."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 2**32, 2048, dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, 2048, dtype=np.uint32))
    for op in ("and", "or", "andnot"):
        w, c = ops.bitset_op(a, b, op, interpret=True)
        rw, rc = ref.bitset_op_ref(a, b, op)
        assert (np.asarray(w) == np.asarray(rw)).all()
        assert int(c) == int(rc)


# ---------------------------------------------------------------------------
# empty-cohort statistics: every aggregation must be total and NaN-free,
# returning the documented sentinels when a denominator count is zero
# ---------------------------------------------------------------------------
def _empty_cohort(n=16):
    ev = make_events(
        patient_id=jnp.zeros((4,), jnp.int32),
        category=Category.DRUG_DISPENSE,
        value=jnp.zeros((4,), jnp.int32),
        start=jnp.zeros((4,), jnp.int32),
        valid=jnp.zeros((4,), bool),           # zero valid events
    )
    return Cohort(name="empty", description="empty", events=ev,
                  subjects=jnp.zeros((Bitset.n_words(n),), jnp.uint32),
                  n_patients=n)


def _empty_patients():
    from repro.core.columnar import ColumnarTable

    return ColumnarTable.from_columns(
        {"patient_id": np.zeros(4, np.int32),
         "gender": np.zeros(4, np.int32),
         "birth_date": np.zeros(4, np.int32),
         "death_date": np.zeros(4, np.int32)},
        valid=np.zeros(4, bool))


def _assert_finite(v, path):
    if isinstance(v, dict):
        for k, x in v.items():
            _assert_finite(x, f"{path}.{k}")
    elif isinstance(v, (list, tuple)):
        for i, x in enumerate(v):
            _assert_finite(x, f"{path}[{i}]")
    elif isinstance(v, float):
        assert np.isfinite(v), f"{path} is not finite: {v}"


def test_empty_cohort_sentinels():
    from repro.core import stats

    c, p = _empty_cohort(), _empty_patients()
    assert stats.age_mean(c, p) == {"mean": 0.0, "std": 0.0, "n": 0}
    assert stats.gender_ratio(c, p) == {"male_fraction": 0.0, "n": 0}
    assert stats.mean_gap_days(c) == {"mean_gap": 0.0, "pairs": 0}
    assert stats.events_per_patient_percentiles(c) == \
        {"p50": 0, "p90": 0, "p99": 0, "n": 0}


def test_empty_cohort_full_battery_nan_free():
    """The whole registered battery runs over an empty cohort without a
    single NaN/inf anywhere in the output."""
    from repro.core import stats

    c, p = _empty_cohort(), _empty_patients()
    out = stats.compute(c, p)
    assert out                                  # battery did run
    _assert_finite(out, "stats")
    report = stats.report(c, p)
    assert "nan" not in report.lower()


def test_nonempty_stats_keep_values():
    """The guards must not disturb populated cohorts."""
    from repro.core import stats

    n = 16
    ev = make_events(
        patient_id=jnp.asarray([1, 1, 2, 3], jnp.int32),
        category=Category.DRUG_DISPENSE,
        value=jnp.asarray([5, 6, 5, 7], jnp.int32),
        start=jnp.asarray([10, 40, 20, 30], jnp.int32),
        valid=jnp.ones((4,), bool),
    )
    c = Cohort.from_events("pop", ev, n)
    g = stats.mean_gap_days(c)
    assert g["pairs"] == 1 and g["mean_gap"] == 30.0   # patient 1: 10 -> 40
    pct = stats.events_per_patient_percentiles(c)
    assert pct["n"] == 3 and pct["p50"] == 1
