"""Pallas TPU kernel: fused Expr-predicate evaluation to a packed bitset.

The extractor hot path (paper §4, Fig. 2) is one mask pass per scan branch.
PR 3 fused each branch's predicate chain into a single ``Expr`` conjunction,
but the executor still evaluated it as jnp mask algebra — one HBM round-trip
per column reference plus a materialized bool column (1 byte/row) that every
consumer re-reads.  This module compiles the serialized Expr tree into ONE
Pallas kernel:

  * one grid pass over the projected columns, streamed as lane-dense
    ``(rows/128, 128)`` tiles — every leaf op (comparisons, arithmetic,
    ``isin`` via an SMEM whitelist scan, sentinel null tests,
    ``&``/``|``/``~``) evaluates entirely in VMEM;
  * the output is a **packed uint32 bitset** (1 bit/row, 8x smaller than a
    bool column): each tile's row mask is packed in VMEM
    (``kernels.pack_tile``) and ANDed with the packed input validity, so
    the mask pass never writes a bool column, and the words use the shared
    ``core.bitset`` layout.
    Since the bitset-native validity redesign, ``ColumnarTable.valid`` IS
    this packed form, so the kernel's output becomes the downstream table's
    validity verbatim — no unpack hop — and both the input validity and the
    result cross HBM at 1 bit/row into the cohort algebra
    (``bitset_ops``) and the compaction keep-mask (``filter_compact``).

Codegen is trace-time: ``compile_predicate`` walks the hashable param tree
(``expr.Expr.to_param`` form — the exact object plan nodes carry) and emits a
closure of jnp ops; ``pallas_call`` then lowers that closure per tile.  The
``isin`` whitelists are static plan params, handed to the kernel as SMEM
operands; membership ORs one scalar-vector compare per whitelist value.

**Hoisted literals are kernel operands** (the normalized-plan path): a
``("hlit", slot)`` leaf becomes a ``(1,)`` SMEM scalar parameter and a
``("hisin", x, slot, n, isfloat)`` whitelist an ``(n,)`` SMEM operand
staged *inside* the jit.  The compiled kernel is
therefore value-generic: two tenants' queries differing only in literals
share one executable, and ``normalize()`` no longer demotes hoisted pallas
predicates to the jnp engine (oversized whitelists and non-boolean roots
remain the only demotion causes).

Grid blocks are independent (`parallel` semantics); the wrapper pads ragged
tails with invalid rows, so any capacity works.
"""
from __future__ import annotations

import functools
import operator as _op
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401 (TPU lowering)

from repro.kernels import (BLOCK_QUANTUM, LANES, default_interpret,
                           pack_tile, round_block)

__all__ = [
    "DEFAULT_BLOCK", "MAX_ISIN_VALUES", "PREDICATE_ENGINES", "compilable",
    "compile_predicate", "default_interpret", "isin_smem_bytes",
    "predicate_bitset", "resolve_engine",
]

DEFAULT_BLOCK = BLOCK_QUANTUM  # rows per grid block

# an isin whitelist is an SMEM operand scanned once per tile (one compare per
# value and row); past this size the kernel's SMEM and per-row cost grow with
# the list, so bigger whitelists stay on the jnp engine
MAX_ISIN_VALUES = 1024

# mirrors columnar.NULL_INT (kernels stay import-light: no repro.core deps,
# same convention as filter_compact's _INT_MIN)
_NULL_INT = -2_147_483_648 + 1

_CMP = {"==": _op.eq, "!=": _op.ne, "<": _op.lt, "<=": _op.le,
        ">": _op.gt, ">=": _op.ge}
_ARITH = {"+": _op.add, "-": _op.sub, "*": _op.mul,
          "//": _op.floordiv, "%": _op.mod}

# param tags whose value is boolean — the kernel packs bits, so the tree ROOT
# must be one of these (interior arithmetic is unrestricted)
_BOOL_TAGS = frozenset({"cmp", "bool", "not", "isin", "hisin",
                        "isnull", "notnull"})

# ---------------------------------------------------------------------------
# engine selection
# ---------------------------------------------------------------------------
PREDICATE_ENGINES = ("jnp", "pallas", "auto")


def resolve_engine(predicate_engine: Optional[str] = None,
                   engine: str = "xla") -> str:
    """Resolve the predicate engine for ``fused_mask``/``predicate`` nodes.

    ``"jnp"``/``"pallas"`` are explicit; ``"auto"`` (or ``None``) picks the
    Pallas bitset kernel when the global executor engine is already
    ``"pallas"`` or when running on a real TPU backend — the same
    backend-derived choice ``ops.default_interpret`` makes for compaction —
    and falls back to jnp mask algebra otherwise.
    """
    pe = predicate_engine or "auto"
    if pe not in PREDICATE_ENGINES:
        raise ValueError(f"predicate engine must be one of {PREDICATE_ENGINES}, "
                         f"got {pe!r}")
    if pe != "auto":
        return pe
    if engine == "pallas" or jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


def _isin_sizes(p, out: list) -> None:
    if not isinstance(p, tuple) or not p:
        return
    if p[0] == "isin":
        out.append(len(p[2]))
        _isin_sizes(p[1], out)
        return
    if p[0] == "hisin":
        out.append(int(p[3]))          # structural size: the hoisted operand
        _isin_sizes(p[1], out)         # carries exactly n values
        return
    for x in p[1:]:
        _isin_sizes(x, out)


def isin_smem_bytes(n_values: int) -> int:
    """SMEM bytes one ``isin`` whitelist of ``n_values`` 32-bit entries
    occupies as a kernel operand.  The static analyzer quotes this in its
    engine-feasibility diagnostics so an oversized whitelist comes with the
    budget it would blow."""
    return 4 * max(int(n_values), 1)


def compilable(expr_param) -> bool:
    """True when the serialized Expr can compile to the bitset kernel:

      * the root must be boolean-valued (packing bits of an arithmetic value
        would be meaningless), and
      * every ``isin``/``hisin`` whitelist must fit the membership budget
        (``MAX_ISIN_VALUES``).  Hoisted whitelists count their structural
        size ``n`` — the operand carries exactly that many values.

    Hoisted slot refs (``hlit``/``hisin``) are kernel *operands* — SMEM
    scalars and SMEM whitelists — so normalized plans compile too.
    Non-compilable exprs stay on the jnp engine (``assign_engines`` stamps
    them back; the executor double-checks; ``normalize`` demotes hoisted
    pallas nodes only when this predicate says no)."""
    if not (isinstance(expr_param, tuple) and len(expr_param) > 0
            and expr_param[0] in _BOOL_TAGS):
        return False
    sizes: list = []
    _isin_sizes(expr_param, sizes)
    return all(s <= MAX_ISIN_VALUES for s in sizes)


# ---------------------------------------------------------------------------
# Expr-param -> kernel-body codegen
# ---------------------------------------------------------------------------
def _is_null(v: jax.Array) -> jax.Array:
    if jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.isnan(v)
    return v == jnp.asarray(_NULL_INT, v.dtype)


def _member(x: jax.Array, tbl, n: int) -> jax.Array:
    """x ∈ tbl for an SMEM whitelist ref of ``n`` values: one scalar-vector
    compare per value, ORed into an int32 accumulator (the TPU compiler
    carries no bool vectors through a loop).  NaN probes and NaN entries
    never compare equal -> non-member, matching ``jnp.isin``."""
    def body(k, acc):
        return acc | (x == tbl[k]).astype(jnp.int32)

    return jax.lax.fori_loop(0, n, body, jnp.zeros(jnp.shape(x), jnp.int32),
                             unroll=n <= 8) != 0


def compile_predicate(expr_param: Tuple):
    """Compile a serialized Expr (``Expr.to_param`` nested tuples) into
    ``(columns, isin_tables, eval_fn, lit_slots, vec_slots)``.

    ``columns`` is the ordered tuple of column operands (the kernel's
    projected inputs); ``isin_tables`` holds one numpy whitelist per ``isin``
    leaf; ``lit_slots`` is the ordered tuple of ``hlit`` slot ids the expr
    reads (each becomes an SMEM scalar parameter) and ``vec_slots`` the
    ordered ``(slot, n, isfloat)`` triples of its ``hisin`` leaves (each an
    SMEM whitelist operand).  ``eval_fn(env, tables, lits, vecs)`` maps
    {column: tile array} + whitelist refs + {slot: scalar} + {slot:
    whitelist ref} to the boolean mask tile — traceable inside a Pallas
    kernel body.
    """
    columns: List[str] = []
    tables: List[np.ndarray] = []
    lit_slots: List[int] = []
    vec_slots: List[Tuple[int, int, bool]] = []

    def walk(p) -> Callable:
        tag = p[0]
        if tag == "col":
            name = p[1]
            if name not in columns:
                columns.append(name)
            return lambda env, tbls, lits, vecs: env[name]
        if tag == "lit":
            v = p[1]
            return lambda env, tbls, lits, vecs: v
        if tag == "hlit":
            slot = int(p[1])
            if slot not in lit_slots:
                lit_slots.append(slot)
            return lambda env, tbls, lits, vecs: lits[slot]
        if tag == "cmp":
            f, l, r = _CMP[p[1]], walk(p[2]), walk(p[3])
            return lambda env, tbls, lits, vecs: f(l(env, tbls, lits, vecs),
                                                   r(env, tbls, lits, vecs))
        if tag == "arith":
            f, l, r = _ARITH[p[1]], walk(p[2]), walk(p[3])
            return lambda env, tbls, lits, vecs: f(l(env, tbls, lits, vecs),
                                                   r(env, tbls, lits, vecs))
        if tag == "bool":
            l, r = walk(p[2]), walk(p[3])
            if p[1] == "and":
                return lambda env, tbls, lits, vecs: (
                    l(env, tbls, lits, vecs) & r(env, tbls, lits, vecs))
            return lambda env, tbls, lits, vecs: (
                l(env, tbls, lits, vecs) | r(env, tbls, lits, vecs))
        if tag == "not":
            x = walk(p[1])
            return lambda env, tbls, lits, vecs: ~x(env, tbls, lits, vecs)
        if tag in ("isnull", "notnull"):
            x = walk(p[1])
            if tag == "notnull":
                return lambda env, tbls, lits, vecs: ~_is_null(
                    jnp.asarray(x(env, tbls, lits, vecs)))
            return lambda env, tbls, lits, vecs: _is_null(
                jnp.asarray(x(env, tbls, lits, vecs)))
        if tag == "isin":
            x = walk(p[1])
            vals = p[2]
            if not vals:   # empty whitelist matches nothing
                return lambda env, tbls, lits, vecs: jnp.zeros(
                    jnp.shape(jnp.asarray(x(env, tbls, lits, vecs))), bool)
            dt = np.float32 if any(isinstance(c, float) for c in vals) \
                else np.int32
            ti = len(tables)
            tables.append(np.asarray(vals, dt))
            return lambda env, tbls, lits, vecs: _member(
                jnp.asarray(x(env, tbls, lits, vecs)), tbls[ti], len(vals))
        if tag == "hisin":
            x = walk(p[1])
            slot, n, isfloat = int(p[2]), int(p[3]), bool(p[4])
            if n == 0:     # empty whitelist matches nothing (no operand)
                return lambda env, tbls, lits, vecs: jnp.zeros(
                    jnp.shape(jnp.asarray(x(env, tbls, lits, vecs))), bool)
            if slot not in [s for s, _, _ in vec_slots]:
                vec_slots.append((slot, n, isfloat))
            return lambda env, tbls, lits, vecs: _member(
                jnp.asarray(x(env, tbls, lits, vecs)), vecs[slot], n)
        raise ValueError(f"unknown Expr param tag {tag!r}")

    if expr_param[0] not in _BOOL_TAGS:
        raise ValueError(
            f"pallas predicate engine needs a boolean-valued expression root, "
            f"got tag {expr_param[0]!r} (use the jnp engine)")
    eval_fn = walk(expr_param)
    return (tuple(columns), tuple(tables), eval_fn,
            tuple(lit_slots), tuple(vec_slots))


# ---------------------------------------------------------------------------
# kernel + wrapper
# ---------------------------------------------------------------------------
def _make_kernel(eval_fn: Callable, names: Sequence[str], n_tables: int,
                 vec_slot_ids: Sequence[int], lit_slot_ids: Sequence[int],
                 lit_bool: Sequence[bool]):
    """Kernel ref order: [cols...] [static isin tables...] [hoisted isin
    vectors...] [hoisted lit scalars...] [packed valid] | [words].  Columns
    are ``(block/128, 128)`` VMEM blocks, whitelists and literals SMEM
    refs.  Bool lits are staged as int32 (SMEM-safe) and cast back here."""
    def _kernel(*refs):
        k = len(names)
        col_refs = refs[:k]
        tbl_refs = refs[k:k + n_tables]
        k += n_tables
        vec_refs = refs[k:k + len(vec_slot_ids)]
        k += len(vec_slot_ids)
        lit_refs = refs[k:k + len(lit_slot_ids)]
        valid_ref, words_ref = refs[-2:]

        vecs = dict(zip(vec_slot_ids, vec_refs))
        lits = {s: (r[0] != 0 if b else r[0])
                for s, b, r in zip(lit_slot_ids, lit_bool, lit_refs)}
        # validity arrives PACKED (1 bit/row of HBM) and is ANDed word-wise:
        # the row mask of the expression is packed, never the validity
        valid = valid_ref[...]
        out = []
        for t in range(valid.shape[0] // 4):         # static tile loop
            rows = slice(LANES * t, LANES * (t + 1))
            env = {nm: r[rows, :] for nm, r in zip(names, col_refs)}
            m = jnp.broadcast_to(eval_fn(env, tbl_refs, lits, vecs),
                                 (LANES, LANES))
            out.append(pack_tile(m) & valid[4 * t:4 * t + 4, :])
        words_ref[...] = jnp.concatenate(out, axis=0)

    return _kernel


def _stage_hoisted(lit_slots: Sequence[int],
                   vec_slots: Sequence[Tuple[int, int, bool]],
                   params: Tuple[Dict[int, jax.Array], Dict[int, jax.Array]]):
    """Stage bound ``{slot: value}`` maps as kernel operands (traced — runs
    inside the jit): each ``hlit`` slot becomes a ``(1,)`` scalar (bools as
    int32, SMEM has no bool lanes) and each ``hisin`` slot its ``(n,)``
    whitelist."""
    b_lits, b_vecs = params
    lit_ops, lit_bool = [], []
    for slot in lit_slots:
        v = jnp.asarray(b_lits[slot])
        isb = v.dtype == jnp.bool_
        lit_bool.append(isb)
        lit_ops.append(v.reshape(1).astype(jnp.int32) if isb
                       else v.reshape(1))
    vec_ops = []
    for slot, n, _ in vec_slots:
        v = jnp.asarray(b_vecs[slot])
        if v.shape != (n,):
            raise ValueError(f"hoisted whitelist slot {slot}: bound value "
                             f"has shape {v.shape}, expr expects ({n},)")
        vec_ops.append(v)
    return lit_ops, tuple(lit_bool), vec_ops


def predicate_bitset_blocks(expr_param: Tuple, cols: Dict[str, jax.Array],
                            valid_words: jax.Array, block: int = DEFAULT_BLOCK,
                            interpret: Optional[bool] = None,
                            params: Tuple[Dict, Dict] = ({}, {})):
    """One fused pass: evaluate ``expr_param`` over ``cols`` AND the packed
    ``valid_words`` bitset (``core.bitset`` layout — validity is streamed at
    1 bit/row, not a bool column).

    Returns the packed uint32 bitset (n/32 words).  Column length must be a
    multiple of ``block``, itself a multiple of ``BLOCK_QUANTUM`` = 32,768
    rows (``predicate_bitset`` pads); ``valid_words`` holds exactly n/32
    words.
    ``params`` is the bound ``(lits, vecs)`` pair backing any hoisted slot
    refs in the expr.
    """
    interpret = default_interpret() if interpret is None else interpret
    assert block % BLOCK_QUANTUM == 0, block
    n = valid_words.shape[0] * 32
    assert n % block == 0, (n, block)
    names, tables, eval_fn, lit_slots, vec_slots = compile_predicate(
        expr_param)
    missing = [nm for nm in names if nm not in cols]
    if missing:
        raise KeyError(f"predicate reads absent column(s) {missing}")
    lit_ops, lit_bool, vec_ops = _stage_hoisted(lit_slots, vec_slots, params)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec((block // LANES, LANES), lambda g: (g, 0))
                for _ in names]
    # whitelists and scalar literals are grid-invariant SMEM operands
    in_specs += [smem] * (len(tables) + len(vec_ops) + len(lit_ops))
    words_spec = pl.BlockSpec((block // 32 // LANES, LANES), lambda g: (g, 0))
    in_specs += [words_spec]
    operands = ([cols[nm].reshape(-1, LANES) for nm in names]
                + [jnp.asarray(t) for t in tables]
                + vec_ops + lit_ops
                + [jax.lax.bitcast_convert_type(
                    valid_words.astype(jnp.uint32), jnp.int32
                ).reshape(-1, LANES)])
    words = pl.pallas_call(
        _make_kernel(eval_fn, names, len(tables),
                     [s for s, _, _ in vec_slots], lit_slots, lit_bool),
        grid=(n // block,),
        in_specs=in_specs,
        out_specs=words_spec,
        out_shape=jax.ShapeDtypeStruct((n // 32 // LANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    return jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(-1)


def _pad_to(x: jax.Array, mult: int, fill=0):
    n = x.shape[0]
    p = (-n) % mult
    if p == 0:
        return x
    return jnp.concatenate([x, jnp.full((p,), fill, x.dtype)])


@functools.partial(jax.jit,
                   static_argnames=("expr_param", "block", "interpret", "n"))
def _predicate_bitset_jit(columns: Dict[str, jax.Array], words: jax.Array,
                          params: Tuple[Tuple, Tuple], *,
                          expr_param: Tuple, block: int,
                          interpret: Optional[bool], n: int):
    if n == 0:
        return jnp.zeros((0,), jnp.uint32), jnp.int32(0)
    block = round_block(block)
    cols = {nm: _pad_to(c, block) for nm, c in columns.items()}
    wp = _pad_to(words, block // 32)
    out = predicate_bitset_blocks(expr_param, cols, wp, block=block,
                                  interpret=interpret, params=params)
    out = out[: (n + 31) // 32]
    return out, jax.lax.population_count(out).sum(dtype=jnp.int32)


def predicate_bitset(columns: Dict[str, jax.Array], valid: jax.Array, *,
                     expr_param: Tuple, block: int = DEFAULT_BLOCK,
                     interpret: Optional[bool] = None,
                     capacity: Optional[int] = None,
                     params: Optional[Tuple[Tuple, Tuple]] = None):
    """Fused predicate -> packed bitset over a table's columns.

    ``valid`` is the table's validity: the canonical packed uint32 word form
    (``ColumnarTable.valid``) or a legacy ``(n,) bool`` row mask, which is
    packed at the boundary.  Returns ``(words, count)``: ``words`` is the
    ceil(n/32)-word uint32 bitset of ``valid & expr`` (row i lives at word
    i//32, bit i%32 — the shared ``core.bitset`` layout, so the result drops
    straight into the table validity and the cohort algebra kernel),
    ``count`` the total surviving rows.  Columns are padded to the block
    quantum with invalid rows.  Only the columns the expression reads are
    passed into the jit boundary — handing in a whole wide table costs
    nothing extra and never retraces on unrelated columns.  ``capacity``
    names the row count when ``valid`` is packed; it defaults to the first
    column's length.  ``params`` is the bound ``(lits, vecs)`` pair backing
    hoisted slot refs (normalized plans); exprs with ``hlit``/``hisin``
    leaves raise without it — the same contract as evaluating a hoisted
    Expr outside ``expr.bound_params``.  Literal *values* are traced
    operands, so they never retrace or recompile this jit.
    """
    names, _, _, lit_slots, vec_slots = compile_predicate(expr_param)
    b_lits, b_vecs = params if params is not None else ((), ())
    want = max(list(lit_slots) + [-1]), max([s for s, _, _ in vec_slots]
                                            + [-1])
    if want[0] >= len(b_lits) or want[1] >= len(b_vecs):
        raise RuntimeError(
            "expr has hoisted slot refs with no bound value; pass "
            "params=(lits, vecs) (see expr.bound_params)")
    # subset to the slots THIS expr reads — other nodes' literals must not
    # become dead operands of (or retrace triggers for) this executable
    used = ({s: b_lits[s] for s in lit_slots},
            {s: b_vecs[s] for s, _, _ in vec_slots})
    missing = [nm for nm in names if nm not in columns]
    if missing:
        raise KeyError(f"predicate reads absent column(s) {missing}")
    if getattr(valid, "dtype", None) == jnp.uint32:
        if capacity is None:
            if not names:
                raise ValueError("packed valid needs an explicit capacity "
                                 "when the predicate reads no columns")
            capacity = int(columns[names[0]].shape[0])
        words = valid
    else:
        valid = jnp.asarray(valid, bool)
        capacity = int(valid.shape[0])
        from repro.core.bitset import pack as _pack

        words = _pack(valid)
    return _predicate_bitset_jit({nm: columns[nm] for nm in names}, words,
                                 used, expr_param=expr_param, block=block,
                                 interpret=interpret, n=capacity)
