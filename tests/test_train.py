"""Training-runtime tests: optimization progress, checkpoint/restart
determinism, microbatch-accumulation equivalence, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import get_bundle
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update, cosine_lr
from repro.train.train_step import init_train_state, make_train_step
from repro.train.checkpointing import (
    AsyncCheckpointer, latest_step, restore_checkpoint, save_checkpoint,
)
from repro.train.grad_compression import (
    dequantize_int8, ef_compress_step, quantize_int8,
)

ARCH = "xlstm-125m"  # smallest reduced config


def small_batch(cfg, key, B=4, S=32):
    return {"tokens": jax.random.randint(key, (B, S), 3, cfg.vocab_size)}


def test_loss_decreases():
    b = get_bundle(ARCH, reduced=True)
    step = jax.jit(make_train_step(
        b, AdamWConfig(lr_peak=3e-3, warmup_steps=5, total_steps=40)),
        donate_argnums=(0,))
    state = init_train_state(b, jax.random.key(0))
    key = jax.random.key(1)
    batch = small_batch(b.cfg, key)  # overfit one batch
    losses = []
    for t in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]


def test_grad_clip_and_lr_schedule():
    cfg = AdamWConfig(lr_peak=1e-3, warmup_steps=10, total_steps=100)
    assert float(cosine_lr(cfg, jnp.int32(0))) == 0.0
    assert abs(float(cosine_lr(cfg, jnp.int32(10))) - 1e-3) < 1e-9
    assert float(cosine_lr(cfg, jnp.int32(100))) < 1e-4


def test_checkpoint_restart_is_bit_deterministic(tmp_path):
    """Train 6 steps; vs train 3, checkpoint, restore, train 3 — identical."""
    b = get_bundle(ARCH, reduced=True)
    opt = AdamWConfig(lr_peak=1e-3, warmup_steps=2, total_steps=10)
    step = jax.jit(make_train_step(b, opt))
    key = jax.random.key(0)
    batches = [small_batch(b.cfg, jax.random.key(100 + t)) for t in range(6)]

    state_a = init_train_state(b, key)
    for t in range(6):
        state_a, _ = step(state_a, batches[t])

    state_b = init_train_state(b, key)
    for t in range(3):
        state_b, _ = step(state_b, batches[t])
    save_checkpoint(str(tmp_path), 3, state_b, meta={"arch": ARCH})
    assert latest_step(str(tmp_path)) == 3
    restored, manifest = restore_checkpoint(str(tmp_path), 3, state_b)
    assert manifest["arch"] == ARCH
    for t in range(3, 6):
        restored, _ = step(restored, batches[t])

    for a, r in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_async_checkpointer(tmp_path):
    b = get_bundle(ARCH, reduced=True)
    state = init_train_state(b, jax.random.key(0))
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        ck.save(s, state)
    ck.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [20, 30]  # keep=2 GC'd step 10


def test_microbatch_accumulation_matches_full_batch():
    b = get_bundle(ARCH, reduced=True)
    opt = AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    full = jax.jit(make_train_step(b, opt, microbatches=1))
    accum = jax.jit(make_train_step(b, opt, microbatches=2))
    state1 = init_train_state(b, jax.random.key(0))
    state2 = jax.tree.map(jnp.copy, state1)
    batch = small_batch(b.cfg, jax.random.key(5), B=4)
    s1, m1 = full(state1, batch)
    s2, m2 = accum(state2, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-2
    # parameters should agree to accumulation-order tolerance
    diffs = [float(jnp.abs(a.astype(jnp.float32) - c.astype(jnp.float32)).max())
             for a, c in zip(jax.tree.leaves(s1["params"]),
                             jax.tree.leaves(s2["params"]))]
    assert max(diffs) < 5e-2, max(diffs)


def test_quantize_roundtrip_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=4096), jnp.float32)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x).max()
    assert float(err) <= float(s) / 2 + 1e-7


def test_error_feedback_contracts():
    """With error feedback, the accumulated error stays bounded while the
    compressed stream's running sum tracks the true gradient sum."""
    rng = np.random.default_rng(1)
    err = jnp.zeros(1024, jnp.float32)
    true_sum = jnp.zeros(1024, jnp.float32)
    sent_sum = jnp.zeros(1024, jnp.float32)
    for t in range(50):
        g = jnp.asarray(rng.normal(size=1024), jnp.float32)
        sent, err = ef_compress_step(g, err)
        true_sum = true_sum + g
        sent_sum = sent_sum + sent
    # residual equals the remaining error buffer exactly
    np.testing.assert_allclose(np.asarray(true_sum - sent_sum),
                               np.asarray(err), rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(err).max()) < 0.1  # bounded by one quantization bin


def test_elastic_restore_reshapes_nothing_but_layout(tmp_path):
    """Restore with explicit shardings (single device: layout no-op) checks
    the reshard code path."""
    b = get_bundle(ARCH, reduced=True)
    state = init_train_state(b, jax.random.key(0))
    save_checkpoint(str(tmp_path), 1, state)
    sh = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]),
        state)
    restored, _ = restore_checkpoint(str(tmp_path), 1, state, shardings=sh)
    for a, r in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
