"""Serving tests: decode parity with prefill, continuous batcher liveness."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import get_bundle
from repro.serving.batching import ContinuousBatcher, Request
from repro.serving.serve_step import greedy_sample, make_serve_step

ARCH = "qwen2-1.5b"


def test_greedy_decode_matches_prefill_argmax():
    b = get_bundle(ARCH, reduced=True)
    params = b.init(jax.random.key(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, S), 3, b.cfg.vocab_size)
    pre = b.prefill(params, {"tokens": toks})
    want = np.asarray(jnp.argmax(pre[:, -1], axis=-1))

    cache = b.init_cache(B, 32)
    step = jax.jit(make_serve_step(b))
    for t in range(S):
        logits, cache = step(params, cache,
                             {"tokens": toks[:, t:t + 1], "pos": jnp.int32(t)})
    got = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
    np.testing.assert_array_equal(got, want)


def test_continuous_batcher_completes_requests():
    b = get_bundle(ARCH, reduced=True)
    params = b.init(jax.random.key(0))
    engine = ContinuousBatcher(b, params, n_slots=2, kv_len=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=[1] + rng.integers(8, 100, 5).tolist(),
                    max_new=4) for i in range(4)]
    for r in reqs:
        engine.submit(r)
    engine.run(max_steps=200)
    assert all(r.done for r in reqs)
    assert all(1 <= len(r.out) <= 4 for r in reqs)


def test_cache_donation_shape_stability():
    """Repeated decode steps keep one cache allocation (donated buffers)."""
    b = get_bundle(ARCH, reduced=True)
    params = b.init(jax.random.key(0))
    cache = b.init_cache(2, 32)
    step = jax.jit(make_serve_step(b), donate_argnums=(1,))
    toks = jnp.ones((2, 1), jnp.int32) * 5
    for t in range(8):
        _, cache = step(params, cache, {"tokens": toks, "pos": jnp.int32(t)})
    leaves = jax.tree.leaves(cache)
    assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves)
