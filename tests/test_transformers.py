"""Transformer tests: exposures / follow-up / fractures / trackloss against
sequential python oracles (including a hypothesis sweep for exposures)."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Category, DCIR_SCHEMA, exposures, flatten_star, follow_up, fractures,
    make_events, observation_period, sort_events, trackloss,
)
from repro.core.columnar import ColumnarTable, NULL_INT
from repro.data.synthetic import SyntheticConfig, generate_dcir


def events_from(pids, vals, starts, cat=Category.DRUG_DISPENSE, valid=None):
    return make_events(
        patient_id=jnp.asarray(pids, jnp.int32),
        category=cat,
        value=jnp.asarray(vals, jnp.int32),
        start=jnp.asarray(starts, jnp.int32),
        valid=None if valid is None else jnp.asarray(valid, bool),
    )


def exposure_oracle(pids, vals, starts, purview):
    """Greedy merge per (patient, drug): the paper's exposure semantics."""
    from collections import defaultdict

    groups = defaultdict(list)
    for p, v, s in zip(pids, vals, starts):
        groups[(p, v)].append(s)
    out = []
    for (p, v), dates in groups.items():
        dates = sorted(dates)
        start = dates[0]
        last = dates[0]
        n = 1
        for d in dates[1:]:
            if d - last <= purview:
                last = d
                n += 1
            else:
                out.append((p, v, start, last + purview, n))
                start = last = d
                n = 1
        out.append((p, v, start, last + purview, n))
    return sorted(out)


def test_exposures_simple():
    ev = events_from([0, 0, 0, 1], [5, 5, 5, 5], [0, 30, 200, 10])
    ex = exposures(ev, n_patients=2, purview_days=60)
    o = ex.to_numpy()
    got = sorted(zip(o["patient_id"], o["value"], o["start"], o["end"],
                     o["weight"].astype(int)))
    want = exposure_oracle([0, 0, 0, 1], [5, 5, 5, 5], [0, 30, 200, 10], 60)
    assert got == want


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 60),
    purview=st.integers(1, 50),
    data=st.data(),
)
def test_property_exposures_oracle(n, purview, data):
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    pids = rng.integers(0, 5, n).tolist()
    vals = rng.integers(0, 4, n).tolist()
    starts = rng.integers(0, 300, n).tolist()
    ex = exposures(events_from(pids, vals, starts), n_patients=5,
                   purview_days=purview)
    o = ex.to_numpy()
    got = sorted(zip(o["patient_id"], o["value"], o["start"], o["end"],
                     o["weight"].astype(int)))
    assert got == exposure_oracle(pids, vals, starts, purview)


def test_observation_period():
    ev = events_from([0, 0, 1], [1, 2, 3], [100, 50, 70])
    obs = observation_period(ev, n_patients=3)
    o = obs.to_numpy()
    assert o["start"][0] == 50 and o["end"][0] == 100
    assert o["start"][1] == 70
    assert len(o["patient_id"]) == 2  # patient 2 has no events


def test_follow_up_death_clips():
    pats = ColumnarTable.from_columns({
        "patient_id": np.asarray([0, 1], np.int32),
        "gender": np.asarray([1, 2], np.int32),
        "birth_date": np.asarray([0, 0], np.int32),
        "death_date": np.asarray([150, int(NULL_INT)], np.int32),
    })
    ev = events_from([0, 1], [1, 1], [100, 100])
    fu = follow_up(pats, ev, n_patients=2, study_end=1000)
    o = fu.to_numpy()
    assert o["end"][0] == 150      # clipped at death
    assert o["end"][1] == 1000     # study end


def test_fractures_washout():
    acts = events_from([0, 0, 0], [2, 2, 2], [0, 30, 200], cat=Category.MEDICAL_ACT)
    diags = events_from([], [], [], cat=Category.DIAGNOSIS)
    fr = fractures(acts, diags, fracture_act_codes=[2], fracture_diag_codes=[],
                   washout_days=90)
    o = fr.to_numpy()
    # events at 0 and 200 kept; 30 is inside the washout of 0
    assert sorted(o["start"].tolist()) == [0, 200]


def test_fractures_per_site_washout_independent():
    # same patient, two body sites (site = value % n_sites)
    acts = events_from([0, 0], [1, 2], [0, 10], cat=Category.MEDICAL_ACT)
    diags = events_from([], [], [], cat=Category.DIAGNOSIS)
    fr = fractures(acts, diags, [1, 2], [], n_sites=8, washout_days=90)
    assert int(fr.count) == 2  # different sites: both kept


def fractures_oracle(acts, diags, act_codes, diag_codes, n_sites, washout):
    """Greedy washout per (patient, site): candidates are the valid acts then
    the valid diagnoses with a fracture code, ordered by (patient, site,
    date) with ties in candidate order; a candidate is kept when it opens a
    new (patient, site) or comes ``washout`` days or more after the last
    kept one.  Rows (patient, code, date, site)."""
    rows = [(*r, act_codes) for r in acts] + [(*r, diag_codes) for r in diags]
    cand = sorted((p, v % n_sites, s, i, v)
                  for i, (p, v, s, ok, codes) in enumerate(rows)
                  if ok and v in codes)
    out, last = [], {}
    for p, site, s, _, v in cand:
        if (p, site) not in last or s - last[(p, site)] >= washout:
            last[(p, site)] = s
            out.append((p, v, s, site))
    return sorted(out)


def _rows(rng, n, n_pat, codes, days, p_valid=1.0):
    return [(int(rng.integers(n_pat)), int(rng.choice(codes)),
             int(rng.integers(days)), bool(rng.random() < p_valid))
            for _ in range(n)]


_FRACTURE_CASES = (
    "no_candidate", "every_row_a_candidate", "invalid_rows_interleaved",
    "equal_dates", "gap_of_exactly_washout", "several_patients_and_sites",
    "one_site", "capacity_not_multiple_of_32", "empty_tables",
)


def _fracture_case(name):
    """(acts rows, diagnoses rows, act codes, diagnosis codes, n_sites,
    washout); a row is (patient, code, date, valid)."""
    rng = np.random.default_rng(_FRACTURE_CASES.index(name))
    if name == "no_candidate":
        return (_rows(rng, 40, 5, [50, 51], 300),
                _rows(rng, 24, 5, [60], 300), [1, 2], [3], 8, 90)
    if name == "every_row_a_candidate":
        return (_rows(rng, 48, 6, [1, 2, 9], 400),
                _rows(rng, 16, 6, [3, 4], 400), [1, 2, 9], [3, 4], 8, 90)
    if name == "invalid_rows_interleaved":
        # invalid rows carry fracture codes and early dates: a visited
        # invalid row would open a chain and drop a valid one
        acts = [(0, 1, 10 * i, i % 2 == 0) for i in range(20)]
        diags = [(0, 1, 10 * i + 5, i % 3 == 0) for i in range(12)]
        return acts, diags, [1], [1], 8, 25
    if name == "equal_dates":
        acts = [(1, 2, 100, True), (1, 2, 100, True), (1, 10, 100, True),
                (1, 2, 189, True), (1, 2, 190, True), (2, 2, 100, True)]
        diags = [(1, 2, 100, True), (1, 2, 190, True)]
        return acts, diags, [2, 10], [2], 8, 90
    if name == "gap_of_exactly_washout":
        acts = [(3, 5, d, True) for d in (0, 89, 90, 179, 180, 269, 271)]
        return acts, [(3, 5, 360, True)], [5], [5], 8, 90
    if name == "several_patients_and_sites":
        return (_rows(rng, 300, 20, list(range(16)) + [99], 700, 0.8),
                _rows(rng, 90, 20, list(range(24)), 700, 0.8),
                list(range(16)), list(range(0, 24, 2)), 8, 60)
    if name == "one_site":
        return (_rows(rng, 200, 12, list(range(10)), 900, 0.7),
                _rows(rng, 60, 12, list(range(10)), 900, 0.7),
                [1, 3, 5, 7], [2, 4], 1, 180)
    if name == "capacity_not_multiple_of_32":
        return (_rows(rng, 37, 4, [1, 2, 3, 8], 365, 0.9),
                _rows(rng, 14, 4, [1, 2, 6], 365, 0.9),
                [1, 2, 8], [2, 6], 4, 45)
    if name == "empty_tables":
        return [], [], [1], [1], 8, 90
    raise KeyError(name)


def _tables(acts, diags):
    col = lambda rows, k: [r[k] for r in rows]
    return tuple(events_from(col(rows, 0), col(rows, 1), col(rows, 2), cat,
                             valid=col(rows, 3))
                 for rows, cat in ((acts, Category.MEDICAL_ACT),
                                   (diags, Category.DIAGNOSIS)))


def _fracture_rows(fr):
    o = fr.to_numpy()
    return sorted(zip(*(o[c].tolist() for c in
                        ("patient_id", "value", "start", "group_id"))))


@pytest.mark.parametrize("case", _FRACTURE_CASES)
def test_fractures_matches_greedy_oracle(case):
    acts, diags, a_codes, d_codes, n_sites, washout = _fracture_case(case)
    fr = fractures(*_tables(acts, diags), a_codes, d_codes, n_sites=n_sites,
                   washout_days=washout)
    want = fractures_oracle(acts, diags, a_codes, d_codes, n_sites, washout)
    assert _fracture_rows(fr) == want
    assert int(fr.count) == len(want)
    n_cand = sum(ok and v in a_codes for _, v, _, ok in acts) + \
        sum(ok and v in d_codes for _, v, _, ok in diags)
    if case == "no_candidate":
        assert n_cand == 0 and want == []
    if case == "every_row_a_candidate":
        assert n_cand == len(acts) + len(diags)
    if case == "capacity_not_multiple_of_32":
        assert (len(acts) + len(diags)) % 32 != 0


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def test_fractures_one_program_for_every_candidate_count():
    rng = np.random.default_rng(7)
    acts = _rows(rng, 120, 9, [1, 2, 3, 4], 500)
    diags = _rows(rng, 40, 9, [1, 2, 5], 500)
    traces = []

    def run(a, d):
        traces.append(1)
        return fractures(a, d, [1, 2, 3], [5], n_sites=4, washout_days=60)

    fn = jax.jit(run)
    counts = []
    for p_valid in (1.0, 0.3):
        valid_a = [(p, v, s, bool(rng.random() < p_valid))
                   for p, v, s, _ in acts]
        valid_d = [(p, v, s, bool(rng.random() < p_valid))
                   for p, v, s, _ in diags]
        fr = fn(*_tables(valid_a, valid_d))
        want = fractures_oracle(valid_a, valid_d, [1, 2, 3], [5], 4, 60)
        assert _fracture_rows(fr) == want
        counts.append(len(want))
    assert counts[0] != counts[1]
    assert len(traces) == 1

    capacity = len(acts) + len(diags)
    eqns = list(_walk_eqns(jax.make_jaxpr(run)(*_tables(acts, diags)).jaxpr))
    prims = [e.primitive.name for e in eqns]
    assert "while" in prims
    assert not [e for e in eqns if e.primitive.name == "scan"
                and e.params["length"] >= capacity]


def test_trackloss():
    ev = events_from([0, 0, 1, 1], [1, 1, 1, 1], [0, 500, 0, 30])
    tl = trackloss(ev, n_patients=2, gap_days=120)
    o = tl.to_numpy()
    assert o["patient_id"].tolist() == [0]
    assert o["start"][0] == 120


def test_end_to_end_dcir_pipeline():
    from repro.core import drug_dispenses

    dcir = generate_dcir(SyntheticConfig(n_patients=100, seed=3))
    flat, _ = flatten_star(DCIR_SCHEMA, dcir)
    drugs = drug_dispenses()(flat)
    ex = exposures(drugs, n_patients=100, purview_days=45)
    assert 0 < int(ex.count) <= int(drugs.count)
    o = ex.to_numpy()
    assert (o["end"] - o["start"] >= 45).all()
