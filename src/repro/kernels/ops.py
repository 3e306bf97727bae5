"""Public jit'd wrappers around the Pallas kernels.

Each wrapper pads inputs to kernel-aligned sizes, invokes the kernel, and
performs the (cheap) cross-block stitches.  ``interpret`` defaults to True
unless running on a real TPU backend — the kernels are TPU-targeted and
validated in interpret mode on CPU (container constraint).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret  # noqa: F401 (shared resolver)
from repro.kernels import filter_compact as _fc
from repro.kernels import segment_scan as _ss
from repro.kernels import bitset_ops as _bo
from repro.kernels import hash_partition as _hp
from repro.kernels import swa_attention as _swa
from repro.kernels.predicate import predicate_bitset  # noqa: F401 (re-export;
# pads + jits itself — see kernels/predicate.py for the Expr->bitset codegen)

__all__ = [
    "default_interpret",
    "filter_compact",
    "segmented_scan",
    "bitset_op",
    "hash_partition_plan",
    "flash_attention",
    "predicate_bitset",
]


def _pad_to(x: jax.Array, mult: int, fill=0):
    n = x.shape[0]
    p = (-n) % mult
    if p == 0:
        return x
    return jnp.concatenate([x, jnp.full((p,) + x.shape[1:], fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def filter_compact(vals: jax.Array, mask: jax.Array,
                   block: int = _fc.DEFAULT_BLOCK,
                   interpret: bool | None = None):
    """Compact ``vals[mask]`` to the front; returns (vals_out, count).

    ``mask`` is a ``(n,) bool`` row mask or — the bitset-native hot path —
    the packed ``(ceil(n/32),) uint32`` keep-mask (``ColumnarTable.valid`` /
    predicate-kernel output; a bool mask is packed at the boundary, so the
    keep mask always streams at 1 bit/row).  The kernel compacts each
    128-row segment; the cross-segment stitch is a single gather driven by
    the cumsum of per-segment counts.
    """
    interpret = default_interpret() if interpret is None else interpret
    n = vals.shape[0]
    if n == 0:
        return vals, jnp.int32(0)
    if getattr(mask, "dtype", None) == jnp.uint32:
        segs, counts = _fc.filter_compact_bits_blocks(
            vals, mask, block=block, interpret=interpret)
    else:
        segs, counts = _fc.filter_compact_blocks(
            vals, mask, block=block, interpret=interpret)
    from repro.core.columnar import cumsum

    seg = _fc.SEGMENT
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            cumsum(counts).astype(jnp.int32)])
    total = offs[-1]
    pos = jnp.arange(n, dtype=jnp.int32)
    blk = jnp.clip(jnp.searchsorted(offs, pos, side="right") - 1, 0,
                   counts.shape[0] - 1)
    src = blk * seg + (pos - offs[blk])
    out = jnp.where(pos < total, segs[jnp.clip(src, 0, segs.shape[0] - 1)],
                    jnp.asarray(0, vals.dtype))
    return out, jnp.minimum(total, n)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segmented_scan(flags: jax.Array, vals: jax.Array, block: int = 512,
                   interpret: bool | None = None):
    """Inclusive segmented (min, max, count) scan; flags start runs."""
    interpret = default_interpret() if interpret is None else interpret
    n = vals.shape[0]
    fp = _pad_to(flags.astype(bool), block, fill=True)
    vp = _pad_to(vals, block)
    mn, mx, ct = _ss.segmented_scan(fp, vp, block=block, interpret=interpret)
    return mn[:n], mx[:n], ct[:n]


@functools.partial(jax.jit, static_argnames=("op", "block", "interpret"))
def bitset_op(a: jax.Array, b: jax.Array, op: str,
              block: int = _bo.DEFAULT_BLOCK, interpret: bool | None = None):
    """Fused bitwise op + total popcount; returns (words, count)."""
    interpret = default_interpret() if interpret is None else interpret
    n = a.shape[0]
    if n == 0:
        return a, jnp.int32(0)
    # the kernel pads ragged tails itself; returns the padded words
    words, partial = _bo.bitset_op_popcount(a, b, op, block=block,
                                            interpret=interpret)
    return words[:n], partial.sum().astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_dest", "block", "interpret"))
def hash_partition_plan(keys: jax.Array, valid: jax.Array, n_dest: int, block: int = 512,
                        interpret: bool | None = None):
    """Shuffle plan: (dest, rank-within-block, per-block histograms)."""
    interpret = default_interpret() if interpret is None else interpret
    n = keys.shape[0]
    kp = _pad_to(keys, block)
    vp = _pad_to(valid.astype(bool), block, fill=False)
    dest, rank, hist = _hp.hash_partition_plan(kp, vp, n_dest, block=block, interpret=interpret)
    return dest[:n], rank[:n], hist


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """Flash attention (GQA, causal, sliding window); pads seq dims to blocks."""
    interpret = default_interpret() if interpret is None else interpret
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if q_offset is None:
        q_offset = Skv - Sq
    bq_ = min(bq, max(8, Sq))
    bk_ = min(bk, max(8, Skv))
    pq = (-Sq) % bq_
    pk = (-Skv) % bk_
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    # kv_len masks padded KV rows in-kernel; padded q rows are discarded on
    # unpad below.
    out = _swa.flash_swa_attention(
        qp, kp, vp, causal=causal, window=window, q_offset=q_offset,
        kv_len=Skv, bq=bq_, bk=bk_, interpret=interpret,
    )
    return out[:, :, :Sq, :]
