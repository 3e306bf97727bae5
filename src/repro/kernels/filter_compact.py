"""Pallas TPU kernel: segment-local stream compaction by a packed keep-mask.

The extractor hot path (paper Fig. 2): after mask algebra, the single
materialization is compacting surviving rows to the front.  On GPU this is a
warp-scan + scattered writes; TPUs have no efficient in-register scatter, so
the TPU-native formulation works on lane-dense ``(rows/128, 128)`` views and
compacts each 128-row *segment* (one sublane row) on its own:

  * the keep-mask arrives PACKED (``core.bitset`` layout, 1 bit/row of HBM)
    and is expanded per tile in VMEM only (``kernels.unpack_tile``);
  * a lane prefix-count (log-step rolls) gives each kept row its shift — the
    number of dropped rows before it — and seven roll-and-select steps move
    every kept row left by that shift, one bit of it per step.  Moving by
    the bits in increasing order never lands two kept rows on one lane, so
    the segment ends with its kept rows at the front, in order;
  * per-segment counts are popcounts of the packed words, so the cross-segment
    stitch — one gather with offsets = cumsum(counts) — runs as fused XLA in
    the wrapper (``ops.filter_compact``).

Grid iterations are independent (`parallel` semantics): this kernel scales to
arbitrarily long columns and is the per-shard body of the distributed
extraction (each mesh shard compacts its patient partition locally).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (BLOCK_QUANTUM, LANES, default_interpret,
                           round_block, unpack_tile)

SEGMENT = LANES                    # rows compacted together
DEFAULT_BLOCK = BLOCK_QUANTUM


def _shifted(x: jax.Array, s: int, fill) -> jax.Array:
    """``y[:, p] = x[:, p - s]`` where ``0 <= p - s < 128``, else ``fill``
    (``s`` may be negative).  Built from lane rotates whose direction is
    read off a rotated lane iota, so it holds under either rotate
    convention."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = s % LANES
    out = jnp.full(x.shape, fill, x.dtype)
    for r in sorted({k, LANES - k}):
        src = pltpu.roll(lane, r, 1)
        out = jnp.where(src == lane - s, pltpu.roll(x, r, 1), out)
    return out


def _compact_rows(v: jax.Array, keep: jax.Array) -> jax.Array:
    """Compact each row of ``v`` (``(R, 128)``) to its front by ``keep``;
    lanes past a row's kept count read 0."""
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    kept = keep.astype(jnp.int32)
    shift = 1
    while shift < LANES:           # inclusive lane prefix-count of kept rows
        kept = kept + _shifted(kept, shift, 0)
        shift *= 2
    d = jnp.where(keep, lane + 1 - kept, -1)       # dropped rows before it
    for k in range(7):             # 2**7 == LANES
        s = 1 << k
        v_in = _shifted(v, -s, 0)                  # v_in[p] = v[p + s]
        d_in = _shifted(d, -s, -1)
        take = (d_in >= 0) & (((d_in >> k) & 1) == 1)
        stay = (d >= 0) & (((d >> k) & 1) == 0)
        v = jnp.where(take, v_in, v)
        d = jnp.where(take, d_in, jnp.where(stay, d, -1))
    return jnp.where(d >= 0, v, jnp.zeros((), v.dtype))


def _kernel(vals_ref, words_ref, out_ref):
    words = words_ref[...]
    for t in range(vals_ref.shape[0] // LANES):    # static tile loop
        keep = unpack_tile(words[4 * t:4 * t + 4, :])
        rows = slice(LANES * t, LANES * (t + 1))
        out_ref[rows, :] = _compact_rows(vals_ref[rows, :], keep)


def segment_counts(words: jax.Array) -> jax.Array:
    """Kept rows per ``SEGMENT``-row segment of a packed keep-mask whose
    length is a multiple of 4 words."""
    pc = jax.lax.population_count(words.astype(jnp.uint32)).astype(jnp.int32)
    return pc.reshape(-1, SEGMENT // 32).sum(axis=1)


def filter_compact_bits_blocks(vals: jax.Array, words: jax.Array,
                               block: int = DEFAULT_BLOCK,
                               interpret: bool | None = None):
    """Segment-compact ``vals`` by a packed keep-mask bitset.

    ``words`` is the canonical packed uint32 word array
    (``ColumnarTable.valid`` / ``kernels.predicate`` output) —
    ``words[i // 32] >> (i % 32) & 1`` keeps row ``i``.  ``vals`` (a 32-bit
    column) and ``words`` are zero-padded here to a whole number of grid
    blocks (``block`` rows, rounded up to ``BLOCK_QUANTUM`` = 32,768);
    padded rows are dropped.  Returns ``(seg_vals, seg_counts)``:
    ``seg_vals[128*a:]`` holds the ``seg_counts[a]`` surviving rows of
    segment ``a`` at its front.  The padded tail is returned (callers
    slice).
    """
    interpret = default_interpret() if interpret is None else interpret
    n = vals.shape[0]
    if n == 0:
        return jnp.zeros((0,), vals.dtype), jnp.zeros((0,), jnp.int32)
    if jnp.dtype(vals.dtype).itemsize != 4:
        raise TypeError(f"filter_compact streams 32-bit columns, got "
                        f"{vals.dtype}")
    block = round_block(block)
    n_pad = -(-n // block) * block
    vals = jnp.pad(vals, (0, n_pad - n))
    words = words.astype(jnp.uint32)
    words = jnp.pad(words, (0, n_pad // 32 - words.shape[0]))
    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((block // LANES, LANES), lambda g: (g, 0)),
                  pl.BlockSpec((block // 32 // LANES, LANES),
                               lambda g: (g, 0))],
        out_specs=pl.BlockSpec((block // LANES, LANES), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad // LANES, LANES), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(vals.reshape(-1, LANES),
      jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1, LANES))
    return out.reshape(-1), segment_counts(words)


def filter_compact_blocks(vals: jax.Array, mask: jax.Array,
                          block: int = DEFAULT_BLOCK,
                          interpret: bool | None = None):
    """``filter_compact_bits_blocks`` for a ``(n,) bool`` row mask: the mask
    is packed at the boundary, so both entry points share one kernel."""
    from repro.core.bitset import pack

    return filter_compact_bits_blocks(vals, pack(jnp.asarray(mask, bool)),
                                      block=block, interpret=interpret)
