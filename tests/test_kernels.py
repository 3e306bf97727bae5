"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp refs."""
import hypothesis.strategies as st
from hypothesis import given, settings
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# -- filter_compact ------------------------------------------------------------
@pytest.mark.parametrize("n,block", [(256, 256), (1000, 256), (130, 64),
                                     (4096, 512), (64, 64)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_filter_compact_sweep(n, block, dtype):
    if dtype == jnp.int32:
        vals = jnp.asarray(RNG.integers(-10**9, 10**9, n), dtype)
    else:
        vals = jnp.asarray(RNG.normal(size=n), dtype)
    mask = jnp.asarray(RNG.random(n) < 0.37)
    out, cnt = ops.filter_compact(vals, mask, block=block, interpret=True)
    rout, rcnt = ref.filter_compact_ref(vals, mask)
    assert int(cnt) == int(rcnt)
    assert_allclose(np.asarray(out), np.asarray(rout))


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_filter_compact_property(data):
    n = data.draw(st.integers(1, 300))
    vals = jnp.asarray(RNG.integers(0, 10**6, n), jnp.int32)
    mask = jnp.asarray(data.draw(st.lists(st.booleans(), min_size=n, max_size=n)))
    out, cnt = ops.filter_compact(vals, mask, block=64, interpret=True)
    expected = np.asarray(vals)[np.asarray(mask)]
    assert int(cnt) == len(expected)
    assert (np.asarray(out)[: len(expected)] == expected).all()


# -- segmented scan ------------------------------------------------------------
@pytest.mark.parametrize("n,block", [(512, 512), (2048, 512), (700, 128),
                                     (128, 128), (96, 32)])
def test_segment_scan_sweep(n, block):
    flags = jnp.asarray(RNG.random(n) < 0.08).at[0].set(True)
    vals = jnp.asarray(RNG.integers(0, 10**6, n), jnp.int32)
    mn, mx, ct = ops.segmented_scan(flags, vals, block=block, interpret=True)
    rmn, rmx, rct = ref.segmented_scan_ref(flags, vals)
    assert (np.asarray(mn) == np.asarray(rmn)).all()
    assert (np.asarray(mx) == np.asarray(rmx)).all()
    assert (np.asarray(ct) == np.asarray(rct)).all()


def test_segment_scan_single_run_spanning_blocks():
    """One run across many blocks exercises the SMEM carry chain."""
    n, block = 1024, 128
    flags = jnp.zeros(n, bool).at[0].set(True)
    vals = jnp.asarray(RNG.integers(0, 100, n), jnp.int32)
    mn, mx, ct = ops.segmented_scan(flags, vals, block=block, interpret=True)
    assert int(ct[-1]) == n
    assert int(mn[-1]) == int(np.asarray(vals).min())
    assert int(mx[-1]) == int(np.asarray(vals).max())


# -- bitset ---------------------------------------------------------------------
@pytest.mark.parametrize("n", [1024, 4096, 1000, 32])
@pytest.mark.parametrize("op", ["and", "or", "andnot", "xor"])
def test_bitset_sweep(n, op):
    a = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    w, c = ops.bitset_op(a, b, op, interpret=True)
    rw, rc = ref.bitset_op_ref(a, b, op)
    assert (np.asarray(w) == np.asarray(rw)).all()
    assert int(c) == int(rc)


# -- ragged / degenerate edge cases (wrapper + kernel-level padding) ----------
@pytest.mark.parametrize("n", [0, 63, 64, 65, 255, 256, 257])
@pytest.mark.parametrize("kind", ["empty", "all_kept", "all_dropped", "mixed"])
def test_filter_compact_edges(n, kind):
    vals = jnp.asarray(RNG.integers(-10**6, 10**6, n), jnp.int32)
    mask = {"empty": jnp.zeros(n, bool),
            "all_kept": jnp.ones(n, bool),
            "all_dropped": jnp.zeros(n, bool),
            "mixed": jnp.asarray(RNG.random(n) < 0.5)}[kind]
    out, cnt = ops.filter_compact(vals, mask, block=64, interpret=True)
    expected = np.asarray(vals)[np.asarray(mask)]
    assert int(cnt) == len(expected)
    assert (np.asarray(out)[: len(expected)] == expected).all()


@pytest.mark.parametrize("n", [0, 1, 31, 1023, 1024, 1025])
def test_bitset_op_ragged_and_degenerate(n):
    """Kernel-level ragged-tail padding: no block-multiple assert, popcounts
    unpolluted by the zero-padded tail."""
    from repro.kernels import bitset_ops as bo

    a = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, n, dtype=np.uint32))
    for op in ("and", "or", "andnot", "xor"):
        w, c = ops.bitset_op(a, b, op, interpret=True)
        rw, rc = ref.bitset_op_ref(a, b, op)
        assert w.shape == (n,)
        assert (np.asarray(w) == np.asarray(rw)).all()
        assert int(c) == int(rc)
        if n:  # kernel entry point directly (padded tail returned)
            wk, pk = bo.bitset_op_popcount(a, b, op, interpret=True)
            assert (np.asarray(wk)[:n] == np.asarray(rw)).all()
            assert int(np.asarray(pk).sum()) == int(rc)


def test_kernel_interpret_defaults_follow_backend():
    """interpret=None resolves by backend in every kernel module (no more
    hardcoded interpret=True entry points), through the ONE shared helper."""
    import repro.kernels as K
    from repro.kernels import bitset_ops as bo
    from repro.kernels import filter_compact as fc
    from repro.kernels import predicate as pk

    on_cpu = jax.default_backend() != "tpu"
    assert K.default_interpret() == on_cpu
    assert ops.default_interpret is K.default_interpret
    assert pk.default_interpret is K.default_interpret
    # callable without interpret= on any backend
    v = jnp.arange(64, dtype=jnp.int32)
    m = jnp.ones(64, bool)
    out, cnt = fc.filter_compact_blocks(v, m, block=64)   # padded tail back
    assert int(cnt[0]) == 64 and (np.asarray(out)[:64] == np.asarray(v)).all()
    w, p = bo.bitset_op_popcount(v.astype(jnp.uint32),
                                 v.astype(jnp.uint32), "and", block=64)
    assert (np.asarray(w)[:64] == np.asarray(v)).all()
    # the off-path kernels resolve interpret=None the same way
    from repro.kernels import hash_partition as hp
    from repro.kernels import segment_scan as ss

    d, _, _ = hp.hash_partition_plan(v, m, 4, block=64)
    assert d.shape == (64,)
    mn, _, _ = ss.segmented_scan(m, v, block=64)
    assert int(mn[-1]) == 63          # every row starts its own run


# -- hash partition ---------------------------------------------------------------
@pytest.mark.parametrize("n,block,n_dest", [(2048, 512, 8), (512, 128, 16),
                                            (1000, 256, 4)])
def test_hash_partition_sweep(n, block, n_dest):
    keys = jnp.asarray(RNG.integers(0, 10**6, n), jnp.int32)
    valid = jnp.asarray(RNG.random(n) < 0.9)
    d, r, h = ops.hash_partition_plan(keys, valid, n_dest, block=block,
                                      interpret=True)
    rd, rr, rh = ref.hash_partition_plan_ref(
        jnp.pad(keys, (0, (-n) % block)), jnp.pad(valid, (0, (-n) % block)),
        n_dest, block)
    assert (np.asarray(d) == np.asarray(rd)[:n]).all()
    assert (np.asarray(r) == np.asarray(rr)[:n]).all()
    assert (np.asarray(h) == np.asarray(rh)).all()


def test_hash_partition_histogram_consistency():
    n, block, n_dest = 1024, 256, 8
    keys = jnp.asarray(RNG.integers(0, 10**6, n), jnp.int32)
    valid = jnp.ones(n, bool)
    d, r, h = ops.hash_partition_plan(keys, valid, n_dest, block=block,
                                      interpret=True)
    # histogram matches destination counts
    dn = np.asarray(d)
    for dest in range(n_dest):
        assert np.asarray(h)[:, dest].sum() == (dn == dest).sum()


# -- flash attention --------------------------------------------------------------
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal,window",
    [
        (2, 4, 2, 128, 128, 64, True, 0),
        (1, 8, 2, 256, 256, 64, True, 64),
        (2, 4, 4, 1, 384, 64, True, 0),        # decode
        (1, 4, 1, 1, 512, 128, True, 128),     # decode + window
        (2, 2, 2, 96, 96, 32, False, 0),       # bidirectional + padding
        (1, 2, 1, 80, 160, 32, True, 0),       # Sq != Skv (chunked prefill)
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=64, bk=64, interpret=True)
    rout = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert_allclose(np.asarray(out, np.float32), np.asarray(rout, np.float32),
                    rtol=tol, atol=tol)


def test_flash_attention_matches_model_sdpa():
    """Kernel vs the model's XLA attention path (serving parity)."""
    from repro.models import layers as L

    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    xla = L.sdpa(q, k, v, causal=True, window=32, q_positions=pos)
    pallas = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=32, bq=64, bk=64,
        interpret=True,
    ).transpose(0, 2, 1, 3).reshape(B, S, Hq * D)
    assert_allclose(np.asarray(pallas), np.asarray(xla), rtol=3e-5, atol=3e-5)
