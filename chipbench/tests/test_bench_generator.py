"""The benchmark's SNDS generator: deterministic per seed, the paper's rows
per patient, fixed sizes across seeds, Zipf code popularity."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.data import snds

N = 512
SEED = 2 ** 32 + 77


@pytest.fixture(scope="module")
def spec():
    cfg = harness.load_config("snds_paper_study_1chip")
    cfg["n_patients"] = N
    return snds.StarSpec.from_config(cfg)


@pytest.fixture(scope="module")
def host(spec):
    return snds.host_copy(snds.generate(spec, SEED))


def test_same_seed_same_star(spec, host):
    again = snds.host_copy(snds.generate(spec, SEED))
    for t in host:
        for c in host[t]:
            assert np.array_equal(host[t][c], again[t][c]), (t, c)


def test_other_seed_same_sizes_and_dates(spec, host):
    other = snds.host_copy(snds.generate(spec, 5))
    for t in host:
        assert {c: v.size for c, v in host[t].items()} == \
            {c: v.size for c, v in other[t].items()}, t
    # the flow dates and per-patient flow counts are fixed multisets
    assert np.array_equal(np.sort(host["ER_PRS"]["execution_date"]),
                          np.sort(other["ER_PRS"]["execution_date"]))
    assert np.array_equal(
        np.sort(np.bincount(host["ER_PRS"]["patient_id"], minlength=N)),
        np.sort(np.bincount(other["ER_PRS"]["patient_id"], minlength=N)))
    assert not np.array_equal(host["ER_PHA"]["cip13"],
                              other["ER_PHA"]["cip13"])


def test_rows_per_patient_and_schema(spec, host):
    rows = sum(next(iter(t.values())).size for t in host.values())
    assert rows == snds.sizes(spec).rows(N)
    assert abs(rows / N - 1034) < 10
    from repro.core import DCIR_SCHEMA, PMSI_MCO_SCHEMA
    for schema in (DCIR_SCHEMA, PMSI_MCO_SCHEMA):
        for ts in schema.all_tables():
            assert set(host[ts.name]) == set(ts.columns)
            for c, dt in ts.columns.items():
                assert host[ts.name][c].dtype == dt, (ts.name, c)
    counts = np.bincount(host["ER_PRS"]["patient_id"], minlength=N)
    assert counts.min() >= 1
    assert counts.max() > 5 * np.median(counts)      # heavy tail


@pytest.mark.parametrize("table,col,n_codes", [
    ("ER_PHA", "cip13", 16289), ("ER_CAM", "ccam_code", 7000),
    ("MCO_D", "icd_code", 17000)])
def test_zipf_marginals(host, table, col, n_codes):
    v = host[table][col]
    v = v[v != snds.NULL]
    assert v.min() >= 0 and v.max() < n_codes
    h = np.sum(1.0 / np.arange(1, n_codes + 1))
    share0 = np.mean(v == 0)
    assert abs(share0 - 1.0 / h) < 4 * np.sqrt(share0 / v.size) + 1e-3
    # popularity falls with the rank: code 0 beats code 9 beats code 99
    f = np.bincount(v, minlength=n_codes)
    assert f[0] > f[9] > f[99]


def test_null_share(host):
    v = host["ER_PHA"]["cip13"]
    assert 0.005 < np.mean(v == snds.NULL) < 0.015
