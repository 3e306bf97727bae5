"""ColumnarTable unit + property tests (the Parquet-analogue invariants)."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.columnar import ColumnarTable, NULL_INT, is_null


def make_table(vals, valid=None):
    return ColumnarTable.from_columns(
        {"a": np.asarray(vals, np.int32),
         "b": np.asarray(vals, np.int32) * 2},
        valid=None if valid is None else np.asarray(valid, bool),
    )


def test_select_is_metadata_only():
    t = make_table([1, 2, 3])
    s = t.select(["a"])
    assert s.column_names == ("a",)
    assert int(s.count) == 3


def test_filter_narrows_validity_without_movement():
    t = make_table([1, 2, 3, 4])
    f = t.filter(jnp.asarray([True, False, True, False]))
    assert int(f.count) == 2
    # data unmoved
    assert (np.asarray(f.columns["a"]) == [1, 2, 3, 4]).all()


def test_compact_preserves_order():
    t = make_table([5, 6, 7, 8], valid=[False, True, False, True])
    c = t.compact()
    assert int(c.count) == 2
    assert np.asarray(c.columns["a"])[:2].tolist() == [6, 8]
    vb = c.valid_numpy()
    assert vb[:2].all() and not vb[2:].any()
    assert c.valid.dtype == jnp.uint32          # packed-bitset representation


def test_drop_nulls():
    vals = np.asarray([1, int(NULL_INT), 3], np.int32)
    t = ColumnarTable.from_columns({"a": vals})
    d = t.drop_nulls(["a"])
    assert int(d.count) == 2


def test_sort_by_sinks_invalid():
    t = make_table([3, 1, 2, 9], valid=[True, True, True, False])
    s = t.sort_by(["a"])
    assert np.asarray(s.columns["a"])[:3].tolist() == [1, 2, 3]
    assert not s.valid_numpy()[3]


def test_concat_and_pad():
    t1, t2 = make_table([1]), make_table([2, 3])
    c = ColumnarTable.concat([t1, t2])
    assert int(c.count) == 3 and c.capacity == 3
    p = c.pad_to(8)
    assert p.capacity == 8 and int(p.count) == 3


@settings(max_examples=50, deadline=None)
@given(
    vals=st.lists(st.integers(-2**31 + 2, 2**31 - 1), min_size=1, max_size=64),
    data=st.data(),
)
def test_property_filter_compact_roundtrip(vals, data):
    """compact(filter(m)) holds exactly the masked values, in order."""
    mask = data.draw(st.lists(st.booleans(), min_size=len(vals), max_size=len(vals)))
    t = make_table(vals)
    c = t.filter(jnp.asarray(mask)).compact()
    expected = [v for v, m in zip(vals, mask) if m]
    assert int(c.count) == len(expected)
    assert np.asarray(c.columns["a"])[: len(expected)].tolist() == expected


@settings(max_examples=50, deadline=None)
@given(vals=st.lists(st.integers(-10**6, 10**6), min_size=1, max_size=64))
def test_property_sort_matches_numpy(vals):
    t = make_table(vals)
    s = t.sort_by(["a"])
    assert np.asarray(s.columns["a"]).tolist() == sorted(vals)


@settings(max_examples=30, deadline=None)
@given(
    vals=st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=64),
    data=st.data(),
)
def test_property_monitoring_checksum_invariant_under_permutation(vals, data):
    """key_sum/key_xor are order-independent (the no-loss audit relies on it)."""
    perm = data.draw(st.permutations(list(range(len(vals)))))
    t1 = make_table(vals)
    t2 = make_table([vals[i] for i in perm])
    s1 = t1.monitoring_stats("a")
    s2 = t2.monitoring_stats("a")
    assert int(s1["key_sum"]) == int(s2["key_sum"])
    assert int(s1["key_xor"]) == int(s2["key_xor"])
