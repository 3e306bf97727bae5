"""Pallas TPU kernel: segmented scan (min/max/count) with cross-block carry.

The Transformer hot path (paper §3.4): per-patient folds over time-sorted
events — exposure merging, observation periods — are *segmented scans* where a
boundary flag marks the start of each (patient, drug) run.

TPU-native formulation:
  * within a block: log-step Hillis–Steele segmented scan (``log2(B)`` shifted
    ``where``-combines, pure VPU, no data-dependent control flow);
  * across blocks: the TPU grid executes sequentially (``arbitrary``
    dimension semantics), so the inter-block carry lives in SMEM scratch and
    flows left-to-right — the Pallas analogue of a decoupled-lookback scan,
    with determinism for free.

Outputs are *inclusive* running (min, max, count) per element with reset at
flags; run-aggregates are read at the last element of each run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BLOCK = 512
_BIG = 2_000_000_000


def _shift1(x, d, fill):
    """x[i-d] with `fill` for i<d (static d) — a pad+slice the VPU loves."""
    return jnp.concatenate([jnp.full((d,), fill, x.dtype), x[:-d]])


def _kernel(flags_ref, vals_ref, omin_ref, omax_ref, ocnt_ref,
            carry_ref):  # SMEM carry: [boundary_seen, min, max, cnt]
    g = pl.program_id(0)
    f = flags_ref[...] != 0
    v = vals_ref[...]
    B = v.shape[0]

    vmin = v
    vmax = v
    cnt = jnp.ones((B,), jnp.int32)
    fb = f
    d = 1
    while d < B:  # static unroll: log2(B) steps
        pmin = _shift1(vmin, d, _BIG)
        pmax = _shift1(vmax, d, -_BIG)
        pcnt = _shift1(cnt, d, 0)
        # fill=False: positions beyond the block edge carry *no* boundary —
        # the inter-block carry (below) is the sole cross-block mechanism.
        pf = _shift1(fb, d, False)
        vmin = jnp.where(fb, vmin, jnp.minimum(pmin, vmin))
        vmax = jnp.where(fb, vmax, jnp.maximum(pmax, vmax))
        cnt = jnp.where(fb, cnt, pcnt + cnt)
        fb = fb | pf
        d *= 2

    # fold the inter-block carry into the open prefix (elements whose run
    # started in an earlier block, i.e. still no boundary seen).
    @pl.when(g == 0)
    def _init():
        carry_ref[0] = 1          # boundary "seen" before the first block
        carry_ref[1] = _BIG
        carry_ref[2] = -_BIG
        carry_ref[3] = 0

    open_prefix = ~fb             # no boundary in [0, i]: continue prior run
    cmin, cmax, ccnt = carry_ref[1], carry_ref[2], carry_ref[3]
    vmin = jnp.where(open_prefix, jnp.minimum(vmin, cmin), vmin)
    vmax = jnp.where(open_prefix, jnp.maximum(vmax, cmax), vmax)
    cnt = jnp.where(open_prefix, cnt + ccnt, cnt)

    omin_ref[...] = vmin
    omax_ref[...] = vmax
    ocnt_ref[...] = cnt

    # next block's carry = running aggregate at the last element
    carry_ref[1] = vmin[B - 1]
    carry_ref[2] = vmax[B - 1]
    carry_ref[3] = cnt[B - 1]


def segmented_scan(flags: jax.Array, vals: jax.Array, block: int = DEFAULT_BLOCK,
                   interpret: bool | None = None):
    """Inclusive segmented (min, max, count) scan; `flags[i]` starts a run.

    Length must be a multiple of ``block`` (wrapper pads with flag=True).
    ``interpret`` defaults by backend (interpret mode off-TPU).
    """
    interpret = default_interpret() if interpret is None else interpret
    n = vals.shape[0]
    assert n % block == 0, (n, block)
    grid = (n // block,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((block,), lambda g: (g,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((block,), lambda g: (g,)),
            pl.BlockSpec((block,), lambda g: (g,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), vals.dtype),
            jax.ShapeDtypeStruct((n,), vals.dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((4,), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: carry dependency
        ),
    )(flags.astype(jnp.int8), vals)
