"""Pallas TPU kernel: radix histogram + in-block rank for the shuffle.

The flattening exchange (Spark shuffle analogue, DESIGN.md §2) needs, per
row: a destination shard ``hash(key) % n`` and a *rank* — the row's position
among same-destination rows of its block — plus per-(block, dest) histograms
so the wrapper can compute global send offsets with one small cumsum.

TPU-native: the rank is an exclusive prefix sum over the (B × n_dest) one-hot
destination matrix — a log-step scan over VPU lanes; histograms are the
column sums of the same matrix.  No scatters in-kernel; the actual permutation
is one XLA gather in the wrapper, fed by (dest, rank, offsets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

DEFAULT_BLOCK = 512
_MUL = 0x9E3779B1


def _kernel(keys_ref, valid_ref, dest_ref, rank_ref, hist_ref, *, n_dest: int):
    k = keys_ref[...].astype(jnp.uint32)
    v = valid_ref[...] != 0
    B = k.shape[0]

    h = k * jnp.uint32(_MUL)
    h = h ^ (h >> 16)
    dest = jnp.where(v, (h % jnp.uint32(n_dest)).astype(jnp.int32), jnp.int32(n_dest))

    onehot = (
        dest[:, None] == jax.lax.broadcasted_iota(jnp.int32, (B, n_dest), 1)
    ).astype(jnp.int32)
    excl = jnp.cumsum(onehot, axis=0) - onehot      # exclusive per-dest prefix
    rank = jnp.where(v, (excl * onehot).sum(axis=1), 0)

    dest_ref[...] = dest
    rank_ref[...] = rank
    hist_ref[...] = onehot.sum(axis=0)[None, :]


def hash_partition_plan(keys: jax.Array, valid: jax.Array, n_dest: int,
                        block: int = DEFAULT_BLOCK,
                        interpret: bool | None = None):
    """Per-row (dest, in-block rank) + per-block histograms.

    Returns ``(dest (N,), rank (N,), hist (n_blocks, n_dest))``.
    ``N % block == 0`` (wrapper pads with invalid rows).  ``interpret``
    defaults by backend (interpret mode off-TPU).
    """
    interpret = default_interpret() if interpret is None else interpret
    n = keys.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    return pl.pallas_call(
        functools.partial(_kernel, n_dest=n_dest),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((block,), lambda g: (g,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((1, n_dest), lambda g: (g, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((grid[0], n_dest), jnp.int32),
        ],
        interpret=interpret,
    )(keys, valid.astype(jnp.int8))
