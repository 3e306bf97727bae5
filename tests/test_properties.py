"""Cross-cutting property tests (system invariants, hypothesis-driven)."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Bitset, Cohort
from repro.core.columnar import ColumnarTable
from repro.core.flattening import expand_join, flatten_star
from repro.core.schema import PMSI_MCO_SCHEMA
from repro.data.synthetic import SyntheticConfig, generate_pmsi
from repro.models.layers import _hierarchical_rank


# -- MoE dispatch rank ---------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 400),
    e=st.sampled_from([2, 4, 8, 16, 64]),
    block=st.sampled_from([16, 64, 256]),
    data=st.data(),
)
def test_property_hierarchical_rank_oracle(n, e, block, data):
    """rank(i) == #earlier rows routed to the same expert — for any shape."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    fe = jnp.asarray(rng.integers(0, e, n), jnp.int32)
    oh = (fe[:, None] == jnp.arange(e)[None, :]).astype(jnp.int32)
    rank = np.asarray(_hierarchical_rank(oh, fe, block=block))
    cnt = np.zeros(e, int)
    for i, x in enumerate(np.asarray(fe)):
        assert rank[i] == cnt[x], (i, int(x))
        cnt[x] += 1


# -- cohort algebra laws ---------------------------------------------------------
def _cohort(name, s, n):
    idx = jnp.asarray(sorted(s) or [0], jnp.int32)
    valid = jnp.asarray([True] * max(len(s), 1)) if s else jnp.asarray([False])
    return Cohort(name=name, description=name,
                  subjects=Bitset.from_indices(idx, valid, n), n_patients=n)


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 128), data=st.data())
def test_property_de_morgan(n, data):
    """|A \\ (B ∪ C)| == |(A \\ B) \\ C| — fold-order invariance the paper's
    CohortFlow semantics rely on."""
    draw = lambda: set(data.draw(st.lists(st.integers(0, n - 1), max_size=n)))
    A, B, C = _cohort("a", draw(), n), _cohort("b", draw(), n), _cohort("c", draw(), n)
    lhs = A.difference(B.union(C))
    rhs = A.difference(B).difference(C)
    assert lhs.subject_count() == rhs.subject_count()
    assert (np.asarray(lhs.subjects) == np.asarray(rhs.subjects)).all()


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 128), data=st.data())
def test_property_intersection_bounded(n, data):
    draw = lambda: set(data.draw(st.lists(st.integers(0, n - 1), max_size=n)))
    A, B = _cohort("a", draw(), n), _cohort("b", draw(), n)
    inter = A.intersection(B)
    assert inter.subject_count() <= min(A.subject_count(), B.subject_count())
    uni = A.union(B)
    assert uni.subject_count() == (A.subject_count() + B.subject_count()
                                   - inter.subject_count())


# -- flattening conservation -------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_pat=st.integers(20, 120))
def test_property_pmsi_flatten_row_conservation(seed, n_pat):
    """Every (stay, diagnosis, act) combination appears exactly
    max(n_diag,1)·max(n_act,1) times per stay — for any synthetic draw."""
    import collections

    cfg = SyntheticConfig(n_patients=n_pat, seed=seed)
    pmsi = generate_pmsi(cfg)
    flat, stats = flatten_star(PMSI_MCO_SCHEMA, pmsi)
    for s in stats:
        s.assert_no_loss()
    f = flat.to_numpy()
    b = pmsi["MCO_B"].to_numpy()
    d = collections.Counter(pmsi["MCO_D"].to_numpy()["stay_id"].tolist())
    a = collections.Counter(pmsi["MCO_A"].to_numpy()["stay_id"].tolist())
    out = collections.Counter(f["stay_id"].tolist())
    for sid in b["stay_id"].tolist():
        want = max(d.get(sid, 0), 1) * max(a.get(sid, 0), 1)
        assert out[sid] == want, (sid, out[sid], want)


# -- tokenizer round-trip ------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_property_token_stream_event_conservation(data):
    """Every in-vocabulary event appears in the token stream exactly once
    (or is counted as truncated)."""
    from repro.core import Category, FeatureDriver, make_events

    n_pat = data.draw(st.integers(1, 16))
    n_ev = data.draw(st.integers(0, 64))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31 - 1)))
    ev = make_events(
        patient_id=jnp.asarray(rng.integers(0, n_pat, max(n_ev, 1)), jnp.int32),
        category=Category.DRUG_DISPENSE,
        value=jnp.asarray(rng.integers(0, 100, max(n_ev, 1)), jnp.int32),
        start=jnp.asarray(rng.integers(0, 1000, max(n_ev, 1)), jnp.int32),
        valid=jnp.asarray([True] * n_ev + [False] * (max(n_ev, 1) - n_ev)),
    )
    c = Cohort.from_events("e", ev, n_pat)
    c.window = (0, 2_000_000)
    fd = FeatureDriver(c)
    seq_len = data.draw(st.sampled_from([8, 32, 128]))
    toks, _ = fd.token_sequences(seq_len)
    n_emitted = int((np.asarray(toks) > 7).sum())  # non-special tokens
    assert n_emitted + fd.checks["events_truncated"] == n_ev
