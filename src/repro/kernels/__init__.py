"""Pallas TPU kernels for the extractor hot path, plus the tile layout they
share.

Every kernel streams 32-bit columns as lane-dense ``(rows/128, 128)`` views
(a free reshape of a 1-D column) and packed validity in the ``core.bitset``
layout (row ``i`` at word ``i // 32``, bit ``i % 32``).  The two meet in a
*tile* of 16,384 rows: a ``(128, 128)`` block of column values and a
``(4, 128)`` block of words.  Consecutive rows sit along lanes,
so a word gathers 32 adjacent lanes of one sublane row; ``pack_tile`` and
``unpack_tile`` do that regrouping with a transpose and lane gathers, which
the TPU compiler accepts (a lane-splitting reshape it refuses).  Packed words
cross HBM as ``(words/128, 128)`` views too, so a grid block holds whole
``(8, 128)`` word tiles: ``BLOCK_QUANTUM`` = 32,768 rows.
"""
import jax
import jax.numpy as jnp

LANES = 128
BLOCK_QUANTUM = 8 * 32 * LANES     # rows whose words fill one (8, 128) tile


def round_block(block: int) -> int:
    """Rows per grid step: ``block`` rounded up to the block quantum."""
    return max(1, -(-int(block) // BLOCK_QUANTUM)) * BLOCK_QUANTUM


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def unpack_tile(words: jax.Array) -> jax.Array:
    """``(4, 128)`` int32 words of one tile -> ``(128, 128)`` bool row mask.

    Row ``128*r + l`` of the tile is bit ``l % 32`` of word ``4*r + l // 32``,
    and that word sits at ``words[r // 32, 4*(r % 32) + l // 32]``: each
    32-row slab reads one word row, broadcast over sublanes and gathered
    along lanes."""
    a, lane = _iota((32, LANES), 0), _iota((32, LANES), 1)
    idx = 4 * a + lane // 32
    slabs = []
    for s in range(4):
        row = jnp.broadcast_to(words[s:s + 1, :], (32, LANES))
        w = jnp.take_along_axis(row, idx, axis=1, mode="promise_in_bounds")
        slabs.append(((w >> (lane % 32)) & 1) != 0)
    return jnp.concatenate(slabs, axis=0)


def pack_tile(mask: jax.Array) -> jax.Array:
    """``(128, 128)`` bool row mask of one tile -> ``(4, 128)`` int32 words
    (the inverse of ``unpack_tile``).

    The transpose puts the 32 rows of each word along sublanes, where a
    shifted sum packs them: ``w[q, r]`` is word ``4*r + q``.  Word
    ``128*s + u`` then comes from ``w[u % 4, 32*s + u // 4]``, one lane
    gather and a 4-sublane select per output row.  Sums of distinct bits
    are exact in int32 (bit 31 wraps to the sign, as a bitcast would)."""
    xt = mask.astype(jnp.int32).T.reshape(4, 32, LANES)
    w = (xt << _iota((4, 32, LANES), 1)).sum(axis=1)
    q, u = _iota((4, LANES), 0), _iota((4, LANES), 1)
    rows = []
    for s in range(4):
        g = jnp.take_along_axis(w, 32 * s + u // 4, axis=1,
                                mode="promise_in_bounds")
        rows.append(jnp.where(q == u % 4, g, 0).sum(axis=0, keepdims=True))
    return jnp.concatenate(rows, axis=0)


def default_interpret() -> bool:
    """Single source of truth for the kernels' interpret default: the Pallas
    kernels are TPU-targeted and run in interpret mode on any other backend
    (the container-CI case).  Every kernel module resolves ``interpret=None``
    through this helper so the fleet can never disagree."""
    return jax.default_backend() != "tpu"
