"""SCALPEL-Flattening tests: joins vs numpy oracles, temporal slicing
equivalence, monitoring (no-loss) statistics."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.columnar import ColumnarTable, NULL_INT, is_null
from repro.core.flattening import expand_join, flatten_sliced, flatten_star, lookup_join
from repro.core.schema import DCIR_SCHEMA, PMSI_MCO_SCHEMA
from repro.data.synthetic import SyntheticConfig, generate_dcir, generate_pmsi


@pytest.fixture(scope="module")
def dcir():
    return generate_dcir(SyntheticConfig(n_patients=150, seed=7))


@pytest.fixture(scope="module")
def pmsi():
    return generate_pmsi(SyntheticConfig(n_patients=150, seed=7))


def test_lookup_join_matches_numpy(dcir):
    flat, st_ = lookup_join(dcir["ER_PRS"], dcir["ER_PHA"], "flow_id", "flow_id")
    f = flat.to_numpy()
    prs = dcir["ER_PRS"].to_numpy()
    pha = dcir["ER_PHA"].to_numpy()
    lut = dict(zip(pha["flow_id"].tolist(), pha["cip13"].tolist()))
    for i in range(0, len(f["flow_id"]), 97):
        fid = f["flow_id"][i]
        want = lut.get(fid, int(NULL_INT))
        assert f["cip13"][i] == want
    assert int(st_.rows_in) == int(st_.rows_out)
    st_.assert_no_loss()


def test_expand_join_cross_product(pmsi):
    flat, st_ = expand_join(pmsi["MCO_B"], pmsi["MCO_D"], "stay_id", "stay_id",
                            out_capacity=4096)
    f = flat.to_numpy()
    d = pmsi["MCO_D"].to_numpy()
    b = pmsi["MCO_B"].to_numpy()
    # every stay with diagnoses appears exactly count(diags) times;
    # stays without diagnoses appear once with null icd
    import collections
    diag_counts = collections.Counter(d["stay_id"].tolist())
    out_counts = collections.Counter(f["stay_id"].tolist())
    for sid in b["stay_id"].tolist():
        assert out_counts[sid] == max(diag_counts.get(sid, 0), 1)
    st_.assert_no_loss()


def test_expand_join_overflow_detected(pmsi):
    _, st_ = expand_join(pmsi["MCO_B"], pmsi["MCO_D"], "stay_id", "stay_id",
                         out_capacity=8)
    assert int(st_.overflow) > 0
    with pytest.raises(AssertionError):
        st_.assert_no_loss()


def test_flatten_star_row_conservation(dcir):
    flat, stats = flatten_star(DCIR_SCHEMA, dcir)
    # DCIR is block-sparse: N:1 joins preserve the central row count
    assert int(flat.count) == int(dcir["ER_PRS"].count)
    for s in stats:
        s.assert_no_loss()


def test_flatten_pmsi_blowup(pmsi):
    flat, _ = flatten_star(PMSI_MCO_SCHEMA, pmsi)
    # 1:N children blow the row count up (Table 1's phenomenon)
    assert int(flat.count) > int(pmsi["MCO_B"].count)


def test_temporal_slicing_equivalence(dcir):
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    t0, t1 = 14_600, 14_600 + 3 * 365
    sliced, _ = flatten_sliced(DCIR_SCHEMA, dcir, "execution_date", 5, t0, t1)
    assert int(sliced.count) == int(flat.count)
    # same multiset of (flow_id) keys
    a = np.sort(flat.to_numpy()["flow_id"])
    b = np.sort(sliced.to_numpy()["flow_id"])
    assert (a == b).all()


@settings(max_examples=25, deadline=None)
@given(
    n_left=st.integers(1, 40),
    n_right=st.integers(0, 40),
    key_range=st.integers(1, 10),
    data=st.data(),
)
def test_property_lookup_join_oracle(n_left, n_right, key_range, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    lk = rng.integers(0, key_range, n_left).astype(np.int32)
    rk = rng.permutation(key_range)[: min(n_right, key_range)].astype(np.int32)
    rv = rng.integers(0, 1000, rk.shape[0]).astype(np.int32)
    left = ColumnarTable.from_columns({"k": lk})
    right = ColumnarTable.from_columns({"k": rk, "v": rv})
    out, _ = lookup_join(left, right, "k", "k")
    lut = dict(zip(rk.tolist(), rv.tolist()))
    o = out.to_numpy()
    for i in range(n_left):
        assert o["v"][i] == lut.get(int(o["k"][i]), int(NULL_INT))
