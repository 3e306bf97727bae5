"""Out-of-core chunked execution: store round-trip, mmap loads, chunked-vs-
resident parity (deterministic battery + hypothesis property over random
plans × chunk sizes), ONE-compile pinning, kill-and-resume, the chunk-unsafe
op guard, SP015, and the shared sharded jit cache."""
import json
import warnings
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import DCIR_SCHEMA, drug_dispenses
from repro.core.columnar import ColumnarTable
from repro.core.extraction import Extractor
from repro.data import (ChunkStore, SyntheticConfig, generate_dcir,
                        load_star, partition_star, save_star)
from repro.data.io import load_columnar_arrays, save_columnar
from repro.study import (Study, clear_jit_cache, col, jit_cache_info)
from repro.study.analyze import analyze
from repro.study.chunked import (ChunkedExecutor, _InjectedCrash,
                                 chunk_unsafe_ops)

N_PAT = 120


@pytest.fixture(scope="module")
def star():
    return generate_dcir(SyntheticConfig(n_patients=N_PAT,
                                         flows_per_patient=5.0, seed=3))


def _study():
    return (Study(n_patients=N_PAT)
            .flatten(DCIR_SCHEMA)
            .extract(drug_dispenses(), name="drugs")
            .patients("IR_BEN")
            .cohort("base", "extract_patients")
            .cohort("drugged", "drugs")
            .cohort("final", "drugged & base")
            .featurize("X", cohort="final", kind="dense",
                       n_buckets=12, bucket_days=31, n_features=64))


def _assert_bit_identical(res, chk, features=True):
    assert set(res.cohorts) == set(chk.cohorts)
    for k, c in res.cohorts.items():
        np.testing.assert_array_equal(np.asarray(c.subjects),
                                      np.asarray(chk.cohorts[k].subjects),
                                      err_msg=f"cohort {k}")
        assert c.subject_count() == chk.cohorts[k].subject_count()
    assert set(res.events) == set(chk.events)
    for k, t in res.events.items():
        a, b = t.to_numpy(), chk.events[k].to_numpy()
        assert set(a) == set(b), k
        for c in a:
            np.testing.assert_array_equal(a[c], b[c],
                                          err_msg=f"events {k}.{c}")
    if features:
        fa, fb = jax.tree.leaves(res.features), jax.tree.leaves(chk.features)
        assert len(fa) == len(fb)
        for u, v in zip(fa, fb):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# ---------------------------------------------------------------------------
# ChunkStore
# ---------------------------------------------------------------------------
def test_partition_roundtrip(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    src = star["ER_PRS"]
    assert store.source == "ER_PRS"
    assert store.manifest.total_rows == int(src.count)
    assert store.n_chunks == -(-src.capacity // 96)
    assert set(store.manifest.resident) == {"ER_PHA", "ER_CAM", "IR_BEN"}
    store.validate()
    # chunk payloads are exactly the source's row slices (32-aligned words)
    full = src.to_numpy()
    got = {c: [] for c in full}
    for ci in range(store.n_chunks):
        t = store.chunk_table(ci, verify=True)
        assert t.capacity == 96
        part = t.to_numpy()
        for c in full:
            got[c].append(part[c])
    for c in full:
        np.testing.assert_array_equal(np.concatenate(got[c]), full[c])
    # key ranges cover valid rows
    for m in store.manifest.chunks:
        assert m.rows <= 96
        if m.rows:
            assert m.key_lo is not None and m.key_lo <= m.key_hi


def test_partition_rejects_misaligned_capacity(star, tmp_path):
    with pytest.raises(ValueError, match="multiple of 32"):
        partition_star(star, str(tmp_path / "s"), source="ER_PRS",
                       chunk_capacity=100)
    with pytest.raises(ValueError, match="multiple of 32"):
        partition_star(star, str(tmp_path / "s"), source="ER_PRS",
                       chunk_capacity=0)


def test_chunk_hash_detects_corruption(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    cols, valid = store.load_chunk_arrays(0, verify=True)   # clean
    doctored = {k: np.array(v) for k, v in cols.items()}
    doctored["patient_id"] = doctored["patient_id"] + 1
    from repro.data.io import save_columnar_arrays

    save_columnar_arrays(doctored, valid, store.chunk_path(0),
                         compressed=False)
    with pytest.raises(IOError, match="hash mismatch"):
        store.load_chunk_arrays(0, verify=True)


def test_partition_from_saved_star_dir_mmap(star, tmp_path):
    sd = str(tmp_path / "star")
    save_star(star, sd, compressed=False)
    a = partition_star(star, str(tmp_path / "a"), source="ER_PRS",
                       chunk_capacity=96)
    b = partition_star(sd, str(tmp_path / "b"), source="ER_PRS",
                       chunk_capacity=96)
    # streaming the saved star through mmap produces the identical store
    assert a.fingerprint() == b.fingerprint()


# ---------------------------------------------------------------------------
# data/io.py mmap pass-through (the satellite bugfix)
# ---------------------------------------------------------------------------
def test_mmap_mode_pass_through(star, tmp_path):
    t = star["IR_BEN"]
    p = str(tmp_path / "t.npz")
    save_columnar(t, p, compressed=False)
    cols, valid = load_columnar_arrays(p, mmap_mode="r")
    # uncompressed members come back memory-mapped, not materialized
    assert all(isinstance(v, np.memmap) for v in cols.values())
    assert isinstance(valid, np.memmap)
    eager_cols, eager_valid = load_columnar_arrays(p)
    assert not any(isinstance(v, np.memmap) for v in eager_cols.values())
    for k in eager_cols:
        np.testing.assert_array_equal(np.asarray(cols[k]), eager_cols[k])
    np.testing.assert_array_equal(np.asarray(valid), eager_valid)


def test_mmap_mode_compressed_fallback(star, tmp_path):
    t = star["IR_BEN"]
    p = str(tmp_path / "t.npz")
    save_columnar(t, p, compressed=True)
    with pytest.warns(RuntimeWarning, match="cannot be memory-mapped"):
        cols, valid = load_columnar_arrays(p, mmap_mode="r")  # degrades eagerly
    assert not any(isinstance(v, np.memmap) for v in cols.values())
    np.testing.assert_array_equal(cols["patient_id"],
                                  np.asarray(t.columns["patient_id"]))


def test_load_star_mmap(star, tmp_path):
    sd = str(tmp_path / "star")
    save_star(star, sd, compressed=False)
    loaded = load_star(sd, mmap_mode="r")
    assert set(loaded) == set(star)
    for k, t in star.items():
        a, b = t.to_numpy(), loaded[k].to_numpy()
        assert set(a) == set(b), k
        for c in a:
            np.testing.assert_array_equal(a[c], b[c], err_msg=f"{k}.{c}")


# ---------------------------------------------------------------------------
# chunked-vs-resident parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk_capacity", [64, 96, 512])
def test_chunked_matches_resident(star, tmp_path, chunk_capacity):
    res = _study().run(star)
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=chunk_capacity)
    chk = _study().run_chunked(store)
    _assert_bit_identical(res, chk)


def test_chunked_concat_preserves_branch_order(star, tmp_path):
    # resident concat lays rows out branch-major ([drugs; acts]) while each
    # chunk emits its own [drugs_ci; acts_ci] — the merge must slice the
    # branches back apart (nested: concat-of-concat flattens the same way)
    def build():
        from repro.core import medical_acts_dcir
        return (Study(n_patients=N_PAT)
                .flatten(DCIR_SCHEMA)
                .extract(drug_dispenses(), name="drugs")
                .extract(medical_acts_dcir(), name="acts")
                .filter("acts", col("value") >= 100, name="acts_hi")
                .concat("pair", "drugs", "acts")
                .concat("triple", "pair", "acts_hi")
                .patients("IR_BEN")
                .cohort("base", "extract_patients")
                .cohort("hit", "pair")
                .flow("hit", "base"))
    res = build().run(star)
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=64)
    assert store.n_chunks > 1
    chk = build().run_chunked(store)
    # _assert_bit_identical compares valid rows IN ORDER per column — the
    # interleaved naive merge fails exactly here on "pair"/"triple"
    _assert_bit_identical(res, chk, features=False)


def test_one_compile_across_all_chunks(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    assert store.n_chunks > 3
    clear_jit_cache()
    rep = {}
    _study().run_chunked(store, report_sink=rep)
    assert rep["executed"] == store.n_chunks
    # fixed chunk capacities => pytree-identical specs => the jit cache
    # serves every chunk after the first from ONE compiled executable
    assert rep["compiles"] == 1
    info = jit_cache_info()
    assert info["compiles"] == 1
    assert info["hits"] == store.n_chunks - 1


def test_kill_and_resume(star, tmp_path):
    res = _study().run(star)
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    ck = str(tmp_path / "ckpt")

    ex = ChunkedExecutor(store, checkpoint_dir=ck, crash_after=2)
    with pytest.raises(_InjectedCrash):
        ex.run(_study())
    assert ex.report.executed == 2
    lines = [json.loads(ln) for ln in open(os.path.join(ck, "journal.jsonl"))]
    assert lines[0]["kind"] == "header"
    assert [ln["index"] for ln in lines[1:]] == [0, 1]

    # crash again mid-resume: completed chunks are NOT re-executed
    ex2 = ChunkedExecutor(store, checkpoint_dir=ck, crash_after=3)
    with pytest.raises(_InjectedCrash):
        ex2.run(_study())
    assert ex2.report.resumed == 2
    assert ex2.report.executed == 3

    ex3 = ChunkedExecutor(store, checkpoint_dir=ck)
    out = ex3.run(_study())
    assert ex3.report.resumed == 5
    assert ex3.report.executed == store.n_chunks - 5
    _assert_bit_identical(res, out)


def test_resume_ignores_foreign_journal(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    ck = str(tmp_path / "ckpt")
    _study().run_chunked(store, checkpoint_dir=ck)
    # a different plan (different predicate) must not adopt the old journal
    other = (Study(n_patients=N_PAT)
             .flatten(DCIR_SCHEMA)
             .extract(drug_dispenses().filtered(col("cip13") >= 3),
                      name="drugs")
             .cohort("drugged", "drugs"))
    rep = {}
    out = other.run_chunked(store, checkpoint_dir=ck, report_sink=rep)
    assert rep["resumed"] == 0
    assert rep["executed"] == store.n_chunks
    ref = other.run(star)
    _assert_bit_identical(ref, out, features=False)


def test_chunk_unsafe_ops_rejected(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    unsafe = (Study(n_patients=N_PAT)
              .flatten(DCIR_SCHEMA)
              .extract(drug_dispenses(), name="drugs")
              .transform("exposures", "drugs", name="exposed",
                         purview_days=60)
              .cohort("exp", "exposed"))
    with pytest.raises(ValueError, match="chunk-unsafe"):
        unsafe.run_chunked(store)
    plan = unsafe.plan()
    assert any(op == "transform" for _, op in
               chunk_unsafe_ops(plan, "ER_PRS"))
    # the escape hatch runs (approximate semantics, documented)
    ChunkedExecutor(store, allow_unsafe=True).run(unsafe)


def test_misaligned_manifest_rejected_statically(star, tmp_path):
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    mpath = os.path.join(store.dirpath, "manifest.json")
    doc = json.load(open(mpath))
    doc["chunk_capacity"] = 100                  # simulate a bad manifest
    json.dump(doc, open(mpath, "w"))
    bad = ChunkStore(store.dirpath)
    with pytest.raises(ValueError, match="multiple of 32"):
        ChunkedExecutor(bad).run(_study())


def test_sp015_diagnostic():
    s = (Study(n_patients=16)
         .patients("IR_BEN")
         .cohort("base", "extract_patients"))
    plan = s.optimized_plan()
    bad = [d for d in analyze(plan, chunk_capacity=100) if d.code == "SP015"]
    assert bad and bad[0].severity == "error"
    assert not [d for d in analyze(plan, chunk_capacity=96)
                if d.code == "SP015"]
    # sharded: the quantum tightens to 32*n_shards
    assert [d for d in analyze(plan, n_shards=2, chunk_capacity=96)
            if d.code == "SP015"]
    assert not [d for d in analyze(plan, n_shards=2, chunk_capacity=128)
                if d.code == "SP015"]


# ---------------------------------------------------------------------------
# shared jit cache: execute_plan_sharded (satellite regression test)
# ---------------------------------------------------------------------------
def test_sharded_executables_share_jit_cache(star):
    from jax.sharding import Mesh

    from repro.distributed.pipeline import execute_plan_sharded

    s = (Study(n_patients=N_PAT)
         .extract(Extractor(name="ev", source="FLAT", category=1,
                            value_col="cip13", start_col="execution_date"),
                  name="ev")
         .cohort("got", "ev"))
    env = {"FLAT": ColumnarTable.from_columns({
        "patient_id": star["ER_PRS"].columns["patient_id"],
        "cip13": star["ER_PRS"].columns["flow_id"],
        "execution_date": star["ER_PRS"].columns["execution_date"]})}
    plan = s.optimized_plan(tables=env)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    clear_jit_cache()
    execute_plan_sharded(plan, env, N_PAT, mesh)
    info = jit_cache_info()
    assert info == {"plans": 1, "compiles": 1, "hits": 0}
    execute_plan_sharded(plan, env, N_PAT, mesh)
    info = jit_cache_info()
    assert info == {"plans": 1, "compiles": 1, "hits": 1}
    clear_jit_cache()
    assert jit_cache_info() == {"plans": 0, "compiles": 0, "hits": 0}


# ---------------------------------------------------------------------------
# hypothesis property: random plans × random chunk sizes
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), cap_words=st.integers(1, 6),
       op=st.sampled_from(["&", "|", "-"]))
def test_property_chunked_parity(tmp_path_factory, seed, cap_words, op):
    rng = np.random.default_rng(seed)
    n_pat = int(rng.integers(8, 40))
    n_rows = int(rng.integers(10, 200))
    # random event table: patients deliberately interleaved so chunk
    # boundaries split a patient's events
    ev = ColumnarTable.from_columns({
        "patient_id": jnp.asarray(rng.integers(0, n_pat, n_rows), jnp.int32),
        "code": jnp.asarray(rng.integers(0, 12, n_rows), jnp.int32),
        "date": jnp.asarray(rng.integers(0, 1000, n_rows), jnp.int32),
    })
    pats = ColumnarTable.from_columns({
        "patient_id": jnp.arange(n_pat, dtype=jnp.int32),
        "gender": jnp.asarray(rng.integers(1, 3, n_pat), jnp.int32),
        "birth_date": jnp.zeros(n_pat, jnp.int32),
        "death_date": jnp.zeros(n_pat, jnp.int32),
    })
    thr = int(rng.integers(0, 13))
    ex = Extractor(name="ev", source="EV", category=1, value_col="code",
                   start_col="date").filtered(col("code") >= thr)

    def build():
        return (Study(n_patients=n_pat)
                .extract(ex, name="ev")
                .patients("PATS")
                .cohort("base", "extract_patients")
                .cohort("got", "ev")
                .cohort("final", f"got {op} base"))

    tables = {"EV": ev, "PATS": pats}
    res = build().run(tables)
    d = tmp_path_factory.mktemp("chunkstore")
    store = partition_star(tables, str(d / "store"), source="EV",
                           chunk_capacity=32 * cap_words)
    chk = build().run_chunked(store)
    _assert_bit_identical(res, chk)


def test_resume_tolerates_torn_journal_tail(star, tmp_path):
    """A kill mid-append leaves a torn final journal line; resume must keep
    every completed line before it (one-chunk cost, not a full restart)."""
    store = partition_star(star, str(tmp_path / "store"), source="ER_PRS",
                           chunk_capacity=96)
    ck = str(tmp_path / "ckpt")
    res = _study().run_chunked(store, checkpoint_dir=ck)
    jp = os.path.join(ck, "journal.jsonl")
    n_done = sum(1 for ln in open(jp) if '"chunk"' in ln)
    assert n_done == store.n_chunks

    # tear the last line mid-record (no trailing newline, invalid JSON)
    with open(jp, "rb") as f:
        raw = f.read()
    torn = raw.rstrip(b"\n")[:-7]
    with open(jp, "wb") as f:
        f.write(torn)
    rep = {}
    out = _study().run_chunked(store, checkpoint_dir=ck, report_sink=rep)
    assert rep["resumed"] == store.n_chunks - 1, \
        "a torn tail must cost exactly the one uncommitted chunk"
    assert rep["executed"] == 1
    _assert_bit_identical(res, out)

    # garbage appended after valid lines: the valid prefix still resumes
    with open(jp, "ab") as f:
        f.write(b'{"kind": "chu')
    rep2 = {}
    out2 = _study().run_chunked(store, checkpoint_dir=ck, report_sink=rep2)
    assert rep2["resumed"] == store.n_chunks
    assert rep2["executed"] == 0
    _assert_bit_identical(res, out2)


def test_mmap_degrade_is_surfaced(star, tmp_path):
    """Compressed members silently degraded to eager reads before; now the
    per-member ``mapped_sink`` flags and a once-per-file RuntimeWarning
    surface it."""
    t = star["IR_BEN"]
    raw = str(tmp_path / "raw.npz")
    packed = str(tmp_path / "packed.npz")
    save_columnar(t, raw, compressed=False)
    save_columnar(t, packed, compressed=True)

    flags = {}
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # mapped loads must not warn
        load_columnar_arrays(raw, mmap_mode="r", mapped_sink=flags)
    assert flags and all(flags.values())
    assert "__valid__" in flags and "patient_id" in flags

    flags = {}
    with pytest.warns(RuntimeWarning, match="cannot be memory-mapped"):
        load_columnar_arrays(packed, mmap_mode="r", mapped_sink=flags)
    assert flags and not any(flags.values())

    # eager loads (no mmap requested): no warning, flags all False
    flags = {}
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        load_columnar_arrays(packed, mapped_sink=flags)
    assert flags and not any(flags.values())
