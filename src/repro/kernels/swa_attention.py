"""Pallas TPU kernel: flash attention with sliding-window + causal masking.

Serving hot-spot for the SWA/local-attention architectures (h2o-danube,
gemma3 local layers, recurrentgemma's local-attn blocks) and the prefill path
generally.  FlashAttention's GPU formulation (shared-memory tiles, warp
reductions) is re-blocked for TPU:

  * KV is streamed block-by-block through VMEM along the innermost
    (sequential) grid dimension; running max / denominator / accumulator live
    in VMEM scratch — the online-softmax recurrence maps to VPU ops, the
    (bq × d)·(d × bk) score product and the (bq × bk)·(bk × d) value product
    hit the MXU at hardware-aligned tile sizes (multiples of 128);
  * GQA is handled in the BlockSpec index maps (q-head -> kv-head integer
    division), so grouped heads share KV traffic;
  * sliding-window blocks fully outside ``[q_pos - window, q_pos]`` are
    skipped with ``pl.when`` — for window ≪ seq this drops compute from
    O(S²) to O(S·W), which is what makes `long_500k` decoding viable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, window: int, causal: bool, q_offset: int,
            bq: int, bk: int, n_kv_blocks: int, kv_len: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    q_lo = iq * bq + q_offset          # first query position of this block
    q_hi = q_lo + bq - 1
    k_lo = ik * bk

    # window/causal/kv-length block-level cull (traced per grid step):
    #   need k_lo <= q_hi (causal), k_lo < kv_len (padding), and
    #   k_lo + bk - 1 >= q_lo - window + 1 (window)
    relevant = k_lo < kv_len
    if causal:
        relevant = relevant & (k_lo <= q_hi)
    if window > 0:
        relevant = relevant & (k_lo + bk - 1 >= q_lo - window + 1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(relevant)
    def _block():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                  # (bq, bk)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len          # padded KV rows are never attended
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _fin():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_swa_attention(
    q: jax.Array,            # (B, Hq, Sq, D)
    k: jax.Array,            # (B, Hkv, Skv, D)
    v: jax.Array,            # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int = 0,         # 0 = no window (full causal)
    q_offset: int | None = None,   # first q position in kv coords (decode)
    kv_len: int | None = None,     # true (unpadded) KV length
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked flash attention; see module docstring.  Sq, Skv must divide by
    (bq, bk) — wrapper in ``ops.py`` pads and unpads.  ``interpret``
    defaults by backend (interpret mode off-TPU)."""
    interpret = default_interpret() if interpret is None else interpret
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if kv_len is None:
        kv_len = Skv
    if q_offset is None:
        q_offset = kv_len - Sq  # decode: queries sit at the end of the cache
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    n_q, n_kv = Sq // bq, Skv // bk
    scale = 1.0 / (D ** 0.5)

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Skv, D)
    vr = v.reshape(B * Hkv, Skv, D)

    def kv_head(bh):
        return (bh // Hq) * Hkv + (bh % Hq) // group

    kern = functools.partial(
        _kernel, scale=scale, window=window, causal=causal,
        q_offset=q_offset, bq=bq, bk=bk, n_kv_blocks=n_kv, kv_len=kv_len,
    )
    out = pl.pallas_call(
        kern,
        grid=(B * Hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (kv_head(bh), ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (kv_head(bh), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)
