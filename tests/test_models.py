"""Per-arch smoke tests (reduced configs, CPU): one forward/train step with
shape + finiteness assertions, decode-vs-parallel consistency, and the
config invariants of the full-size (dry-run-only) configs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS, LONG_CONTEXT_OK
from repro.models import get_bundle, all_archs
from repro.models import lm as LM


KEY = jax.random.key(0)


def make_batch(cfg, B=2, S=32):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 3, cfg.vocab_size)}
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            KEY, (B, max(64, S // 4), cfg.frontend_dim), jnp.bfloat16)
    if cfg.frontend == "vision_patches":
        batch["image_embeds"] = jax.random.normal(
            KEY, (B, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", all_archs())
def test_arch_smoke_train_step(arch):
    b = get_bundle(arch, reduced=True)
    params = b.init(KEY)
    batch = make_batch(b.cfg)
    loss, grads = jax.value_and_grad(b.train_loss)(params, batch)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", all_archs())
def test_arch_smoke_prefill_and_decode(arch):
    b = get_bundle(arch, reduced=True)
    params = b.init(KEY)
    B = 2
    batch = make_batch(b.cfg, B=B)
    pre = b.prefill(params, batch)
    assert pre.shape[0] == B and pre.shape[1] == 1
    assert not np.isnan(np.asarray(pre, np.float32)).any(), arch
    cache = b.init_cache(B, 64)
    logits, new_cache = b.decode(
        params, cache, {"tokens": batch["tokens"][:, :1], "pos": jnp.int32(3)})
    assert logits.shape[:2] == (B, 1)
    assert not np.isnan(np.asarray(logits, np.float32)).any(), arch
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", [
    "llama3.2-3b", "h2o-danube-1.8b", "gemma3-12b", "recurrentgemma-2b",
    "xlstm-125m",
])
def test_decode_matches_parallel(arch):
    """Token-by-token decode with cache == parallel forward (ring buffers,
    recurrent states, GQA, mLSTM recurrent form)."""
    b = get_bundle(arch, reduced=True)
    cfg = b.cfg
    params = b.init(jax.random.key(1))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.key(2), (B, S), 3, cfg.vocab_size)
    full_logits, _ = LM.forward(params, cfg, toks)
    cache = b.init_cache(B, 32)
    dec = jax.jit(b.decode)
    maxerr = 0.0
    for t in range(S):
        logits, cache = dec(params, cache,
                            {"tokens": toks[:, t:t + 1], "pos": jnp.int32(t)})
        e = float(jnp.abs(logits[:, 0].astype(jnp.float32)
                          - full_logits[:, t].astype(jnp.float32)).max())
        maxerr = max(maxerr, e)
    assert maxerr < 0.05, (arch, maxerr)


def test_moe_routing_mass_conserved():
    """Top-k gate weights sum to 1 per token; padded experts get no mass."""
    from repro.models import layers as L

    b = get_bundle("qwen2-moe-a2.7b", reduced=True)
    cfg = b.cfg
    p = L.moe_params(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model), jnp.float32)
    logits = x.reshape(-1, cfg.d_model) @ p["router"]
    pad_mask = jnp.arange(cfg.padded_experts) >= cfg.n_experts
    logits = jnp.where(pad_mask[None], -1e30, logits)
    gates, experts = jax.lax.top_k(logits, cfg.top_k)
    assert int(experts.max()) < cfg.n_experts  # never routes to pad experts
    y = L.moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_vlm_image_positions_masked_in_loss():
    b = get_bundle("phi-3-vision-4.2b", reduced=True)
    cfg = b.cfg
    params = b.init(KEY)
    batch = make_batch(cfg, B=2, S=32)
    # corrupting image-position TOKENS must not change the loss (they are
    # replaced by projected patches and masked out of CE)
    l1 = b.train_loss(params, batch)
    toks2 = batch["tokens"].at[:, : cfg.n_frontend_tokens].set(7)
    l2 = b.train_loss(params, {**batch, "tokens": toks2})
    assert abs(float(l1) - float(l2)) < 1e-5


# ---- full-size config invariants (dry-run-only sizes; no allocation) --------
@pytest.mark.parametrize("arch", all_archs())
def test_full_config_param_counts(arch):
    cfg = ARCHS[arch]
    total = cfg.total_params()
    expected = {
        "deepseek-moe-16b": 16.4e9, "qwen2-moe-a2.7b": 14.3e9,
        "recurrentgemma-2b": 2.7e9, "h2o-danube-1.8b": 1.8e9,
        "llama3.2-3b": 3.2e9, "gemma3-12b": 12e9, "qwen2-1.5b": 1.5e9,
        "xlstm-125m": 0.125e9, "phi-3-vision-4.2b": 3.8e9,
        "seamless-m4t-medium": 1.2e9,
    }[arch]
    assert 0.5 * expected < total < 1.8 * expected, (arch, total, expected)


@pytest.mark.parametrize("arch", all_archs())
def test_full_config_divisibility(arch):
    """Static dims must divide the 16-way model axis (after padding)."""
    cfg = ARCHS[arch]
    assert cfg.padded_vocab % 16 == 0
    if cfg.n_experts:
        assert cfg.padded_experts % 16 == 0
    assert (cfg.n_heads * cfg.head_dim_) % 16 == 0
    assert cfg.d_ff % 16 == 0 or cfg.d_ff == 0
    assert cfg.n_layers - cfg.first_dense_layers >= len(cfg.pattern)


def test_long_context_applicability_table():
    assert LONG_CONTEXT_OK == {
        "recurrentgemma-2b", "h2o-danube-1.8b", "gemma3-12b", "xlstm-125m"}
    for arch in all_archs():
        b = get_bundle(arch)
        from repro.configs.base import SHAPES
        assert b.supports(SHAPES["train_4k"])
        assert b.supports(SHAPES["long_500k"]) == (arch in LONG_CONTEXT_OK)
