"""Transformer tests: exposures / follow-up / fractures / trackloss against
sequential python oracles (including a hypothesis sweep for exposures)."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Category, DCIR_SCHEMA, exposures, flatten_star, follow_up, fractures,
    make_events, observation_period, sort_events, trackloss,
)
from repro.core.columnar import ColumnarTable, NULL_INT
from repro.data.synthetic import SyntheticConfig, generate_dcir


def events_from(pids, vals, starts, cat=Category.DRUG_DISPENSE):
    n = len(pids)
    return make_events(
        patient_id=jnp.asarray(pids, jnp.int32),
        category=cat,
        value=jnp.asarray(vals, jnp.int32),
        start=jnp.asarray(starts, jnp.int32),
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
