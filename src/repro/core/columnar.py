"""ColumnarTable: the TPU-native analogue of SCALPEL3's Parquet-backed tables.

SCALPEL3 stores denormalized claims in Parquet (struct-of-arrays on disk) and
exploits three columnar properties (paper §3.4):
  (1) column projection is a metadata lookup,
  (2) null filtering exploits sparsity (nulls are not materialized),
  (3) row-value filtering happens late, on already-reduced data.

On TPU the equivalent resident format is a struct-of-arrays of fixed-capacity
``jnp`` arrays plus a validity mask.  XLA requires static shapes, so a table
has a *capacity* (allocated rows) and a *count* (valid rows); "null skipping"
becomes mask algebra (masked lanes are never re-materialized), and compaction
is an explicit, vectorized gather (see ``kernels/filter_compact``).

Validity representation: ``valid`` is a **packed uint32 bitset** (row ``i`` at
word ``i // 32``, bit ``i % 32`` — the one layout shared with
``cohort.Bitset`` and the Pallas kernels; see ``core/bitset``).  A validity
word costs 1 bit/row instead of the 1 byte/row of a bool column, so mask
algebra, cohort set-ops and the compaction keep-mask stay memory-bandwidth-
bound on *metadata*; the Pallas predicate kernel's packed output drops into
the table without an unpack hop.  Consumers that need a per-row mask (sorts,
segment folds, host export) call ``valid_bool()`` — the explicit, auditable
expansion boundary.

The class is a registered pytree so tables flow through ``jit``/``shard_map``
unchanged and shard across a mesh ``data`` axis like Spark partitions across
executors.  ``capacity`` is static pytree aux-data (shapes are static under
XLA anyway); the raw constructor accepts a bool row mask for ``valid`` and
packs it at the boundary, so eager call sites migrate incrementally.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset as _bs

__all__ = [
    "ColumnarTable",
    "NULL_INT",
    "NULL_FLOAT",
    "cumsum",
    "is_null",
]

# Sentinel encodings for nulls.  Parquet stores nulls out-of-band (definition
# levels); in fixed-width SoA we reserve a sentinel per dtype and track
# per-column null masks only where a column is declared nullable.
NULL_INT = jnp.int32(-2_147_483_648 + 1)  # INT32_MIN+1, keeps INT32_MIN usable for -inf keys
NULL_FLOAT = jnp.float32(jnp.nan)


def is_null(col: jax.Array) -> jax.Array:
    """Elementwise null mask for a sentinel-encoded column."""
    if jnp.issubdtype(col.dtype, jnp.floating):
        return jnp.isnan(col)
    return col == jnp.asarray(NULL_INT, dtype=col.dtype)


_SCAN_ROW = 1024


def cumsum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D integer array, equal to
    ``jnp.cumsum(x)``: scans of ``_SCAN_ROW``-long rows plus a scan of the
    row totals.  The TPU compiler builds ``jnp.cumsum`` as one window as
    long as the array, taking time that grows with its length (over a
    minute at millions of rows); these short scans compile in about a
    second."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    n = x.shape[0]
    if n <= _SCAN_ROW:
        return jnp.cumsum(x)
    rows = jnp.pad(x, (0, (-n) % _SCAN_ROW)).reshape(-1, _SCAN_ROW)
    inner = jnp.cumsum(rows, axis=1)
    totals = inner[:, -1]
    return (inner + (cumsum(totals) - totals)[:, None]).reshape(-1)[:n]


def _max_key(dtype) -> jax.Array:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.finfo(dtype).max, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarTable:
    """Fixed-capacity struct-of-arrays table with a packed-bitset validity.

    Attributes:
      columns:  name -> (capacity,) array.  All columns share the capacity.
      valid:    (ceil(capacity/32),) uint32 — packed row-validity bitset
                (``core.bitset`` layout; bits >= capacity are always 0).
                A bool ``(capacity,)`` row mask may be passed instead; the
                constructor packs it at the boundary.
      count:    scalar int32 — number of valid rows (== popcount(valid);
                carried so downstream code never re-reduces).
      capacity: static row capacity (pytree aux-data); derived from the
                columns (or a bool mask) when omitted.
    """

    columns: Dict[str, jax.Array]
    valid: jax.Array
    count: jax.Array
    capacity: Optional[int] = None

    def __post_init__(self):
        v = self.valid
        if not _bs.is_packed(v):
            v = jnp.asarray(v, bool)
            if self.capacity is None:
                self.capacity = int(v.shape[0])
            self.valid = _bs.pack(v)
        elif self.capacity is None:
            if not self.columns:
                raise ValueError(
                    "packed validity needs at least one column (or an "
                    "explicit capacity) to recover the row capacity")
            self.capacity = int(next(iter(self.columns.values())).shape[0])

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        names = tuple(sorted(self.columns))
        children = tuple(self.columns[n] for n in names) + (self.valid, self.count)
        return children, (names, self.capacity)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, capacity = aux
        cols = dict(zip(names, children[: len(names)]))
        valid, count = children[len(names)], children[len(names) + 1]
        return cls(cols, valid, count, capacity)

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_columns(cls, columns: Mapping[str, jax.Array],
                     valid: jax.Array | None = None) -> "ColumnarTable":
        """Build a table; ``valid`` may be a ``(capacity,) bool`` row mask OR
        an already-packed ``(ceil(capacity/32),) uint32`` bitset (e.g. a
        predicate-kernel output).  Either form is length-validated against
        the column capacity — a mismatched mask would silently corrupt
        ``count`` and every downstream popcount."""
        cols = {k: jnp.asarray(v) for k, v in columns.items()}
        cap = next(iter(cols.values())).shape[0]
        for k, v in cols.items():
            if v.shape[0] != cap:
                raise ValueError(f"column {k!r} capacity {v.shape[0]} != {cap}")
        if valid is None:
            words = _bs.first_n(cap, cap)
            return cls(dict(cols), words, jnp.int32(cap), int(cap))
        if _bs.is_packed(valid):
            valid = jnp.asarray(valid)
            if valid.shape[0] != _bs.n_words(cap):
                raise ValueError(
                    f"packed valid has {valid.shape[0]} words but capacity "
                    f"{cap} needs {_bs.n_words(cap)}")
            # enforce the tail-bits-clear invariant on caller-supplied words
            valid = valid & _bs.first_n(cap, cap)
            return cls(dict(cols), valid, _bs.count(valid), int(cap))
        valid = jnp.asarray(valid, dtype=bool)
        if valid.shape[0] != cap:
            raise ValueError(
                f"valid mask length {valid.shape[0]} != capacity {cap}")
        return cls(dict(cols), _bs.pack(valid),
                   valid.sum().astype(jnp.int32), int(cap))

    @classmethod
    def empty(cls, spec: Mapping[str, np.dtype], capacity: int) -> "ColumnarTable":
        cols = {k: jnp.zeros((capacity,), dtype=dt) for k, dt in spec.items()}
        valid = jnp.zeros((_bs.n_words(capacity),), jnp.uint32)
        return cls(cols, valid, jnp.int32(0), int(capacity))

    # -- basic properties ----------------------------------------------------
    @property
    def column_names(self) -> tuple:
        return tuple(sorted(self.columns))

    def num_valid(self) -> jax.Array:
        return self.count

    def valid_bool(self) -> jax.Array:
        """Per-row bool validity — the compatibility expansion for consumers
        that need a row mask (sorts, segment folds).  The packed ``valid``
        words are the canonical form; this is a fused bitwise expansion."""
        return _bs.unpack(self.valid, self.capacity)

    def valid_numpy(self) -> np.ndarray:
        """Host-side per-row bool validity (numpy)."""
        return _bs.unpack_np(np.asarray(self.valid), self.capacity)

    def __getitem__(self, name: str) -> jax.Array:
        return self.columns[name]

    # -- columnar ops (paper Fig. 2 steps) ------------------------------------
    def select(self, names: Sequence[str]) -> "ColumnarTable":
        """Step 1 — column projection.  Pure metadata: no data movement."""
        return ColumnarTable({n: self.columns[n] for n in names},
                             self.valid, self.count, self.capacity)

    def with_columns(self, extra: Mapping[str, jax.Array]) -> "ColumnarTable":
        cols = dict(self.columns)
        for k, v in extra.items():
            cols[k] = jnp.asarray(v)
        return ColumnarTable(cols, self.valid, self.count, self.capacity)

    def filter(self, mask: jax.Array) -> "ColumnarTable":
        """Lazy row filter: narrows the validity bitset only (zero data
        movement).  ``mask`` is a ``(capacity,) bool`` row mask or an
        already-packed word array — either way the update is a word-wise AND
        (the columnar analogue of Parquet predicate pushdown; invalid lanes
        stay allocated but are never consumed).
        """
        if _bs.is_packed(mask):
            new_valid = self.valid & mask
        else:
            new_valid = self.valid & _bs.pack(jnp.asarray(mask, bool))
        return ColumnarTable(self.columns, new_valid, _bs.count(new_valid),
                             self.capacity)

    def drop_nulls(self, names: Sequence[str]) -> "ColumnarTable":
        """Step 2 — null filtering via mask algebra (cost ~ metadata)."""
        mask = None
        for n in names:
            ok = ~is_null(self.columns[n])
            mask = ok if mask is None else mask & ok
        if mask is None:
            return self
        return self.filter(mask)

    def compact(self) -> "ColumnarTable":
        """Gather valid rows to the front, preserving order (stream compaction).

        Bitset-native: the inclusive rank of row ``i`` (== the old
        ``cumsum(valid_bool)``) is rebuilt from the packed words — an
        exclusive cumsum of per-word popcounts plus an in-word masked
        popcount — so the keep-mask read is 1 bit/row.  The gather index for
        output slot j is then ``searchsorted(rank, j+1)``; slots past
        ``count`` hold clamped garbage and are masked invalid via a word-wise
        ``first_n``.  The Pallas ``filter_compact`` kernel (bitset keep-mask
        variant) is the fused production path; this is the always-correct jnp
        fallback used inside larger traced programs.
        """
        cap = self.capacity
        if cap == 0:
            return self
        words = self.valid
        per_word = jax.lax.population_count(words).astype(jnp.int32)
        excl = cumsum(per_word) - per_word               # popcount cumsum
        rows = jnp.arange(cap, dtype=jnp.int32)
        w, b = rows >> 5, (rows & 31).astype(jnp.uint32)
        upto = (jnp.uint32(2) << b) - jnp.uint32(1)      # bits <= b (wraps ok)
        within = jax.lax.population_count(words[w] & upto).astype(jnp.int32)
        rank = excl[w] + within                          # inclusive valid rank
        idx = jnp.searchsorted(rank, rows + 1, side="left")
        idx = jnp.minimum(idx, max(cap - 1, 0))
        cols = {k: v[idx] for k, v in self.columns.items()}
        return ColumnarTable(cols, _bs.first_n(self.count, cap), self.count,
                             cap)

    def take(self, idx: jax.Array, idx_valid: jax.Array | None = None) -> "ColumnarTable":
        """Row gather.  ``idx_valid`` marks which gathered rows exist."""
        cols = {k: v[idx] for k, v in self.columns.items()}
        valid = _bs.bit_at(self.valid, idx)
        if idx_valid is not None:
            valid = valid & idx_valid
        return ColumnarTable(cols, valid, valid.sum().astype(jnp.int32))

    def sort_by(self, names: Sequence[str]) -> "ColumnarTable":
        """Stable lexicographic sort; invalid rows sink to the end.

        Bitset-native: the per-row validity bit is gathered straight from
        the packed words (``bitset.bit_at`` — 1 bit/row of HBM, no bool
        column) and folded into the sort keys.  Because invalid rows sink,
        the sorted validity is exactly "first ``count`` rows" — emitted
        word-wise via ``bitset.first_n``, so the sort boundary never expands
        or re-packs a bool mask."""
        if self.capacity == 0:
            return self
        cap = self.capacity
        rows = jnp.arange(cap, dtype=jnp.int32)
        bit = _bs.bit_at(self.valid, rows)
        # Least significant key first: each pass sorts (key, position)
        # pairs, which are all distinct, so even an unstable sort yields the
        # stable order, and the passes compose into the lexicographic one.
        # The TPU compiler builds these two-key sorts several times faster
        # than one stable comparator over every key.
        idx = rows
        for n in reversed(list(names)):
            col = self.columns[n]
            key = jnp.where(bit, col, _max_key(col.dtype))[idx]
            _, order = jax.lax.sort((key, rows), num_keys=2, is_stable=False)
            idx = idx[order]
        # Most significant: invalid rows sink last even if a valid row
        # happens to carry the max key value; (invalid, position) is one
        # distinct int32 key.
        last = jnp.where(bit[idx], 0, cap) + rows
        _, idx = jax.lax.sort((last, idx), num_keys=1, is_stable=False)
        cols = {k: v[idx] for k, v in self.columns.items()}
        return ColumnarTable(cols, _bs.first_n(self.count, self.capacity),
                             self.count, self.capacity)

    def shrink_to(self, capacity: int) -> "ColumnarTable":
        """Truncate to a smaller static capacity (inverse of ``pad_to``).

        Meant for already-compacted tables (valid rows at the front): valid
        rows beyond ``capacity`` are dropped, so callers size ``capacity``
        from the row count and audit the loss (see the ``slice_time`` node's
        overflow statistic).  Capacities >= the current one are a no-op.
        """
        if capacity >= self.capacity:
            return self
        cols = {k: v[:capacity] for k, v in self.columns.items()}
        valid = self.valid[: _bs.n_words(capacity)] & _bs.first_n(capacity,
                                                                  capacity)
        return ColumnarTable(cols, valid, _bs.count(valid), int(capacity))

    def pad_to(self, capacity: int) -> "ColumnarTable":
        if capacity < self.capacity:
            raise ValueError("pad_to cannot shrink a table")
        extra = capacity - self.capacity
        cols = {k: jnp.pad(v, (0, extra)) for k, v in self.columns.items()}
        # word-wise: new rows are invalid; existing tail bits are already 0
        valid = jnp.pad(self.valid,
                        (0, _bs.n_words(capacity) - self.valid.shape[0]))
        return ColumnarTable(cols, valid, self.count, int(capacity))

    @staticmethod
    def concat(tables: Sequence["ColumnarTable"]) -> "ColumnarTable":
        names = tables[0].column_names
        for t in tables[1:]:
            if t.column_names != names:
                raise ValueError("concat: mismatched schemas")
        cols = {n: jnp.concatenate([t.columns[n] for t in tables]) for n in names}
        if all(t.capacity % _bs.WORD_BITS == 0 for t in tables[:-1]):
            # word-aligned fast path (planner capacities are 64-aligned):
            # packed words concatenate directly, no expansion
            valid = jnp.concatenate([t.valid for t in tables])
        else:
            valid = _bs.pack(jnp.concatenate(
                [t.valid_bool() for t in tables]))
        count = sum((t.count for t in tables), jnp.int32(0))
        capacity = sum(t.capacity for t in tables)
        return ColumnarTable(cols, valid, count, capacity)

    # -- monitoring (paper §3.3: statistics proving no information loss) -----
    def monitoring_stats(self, key: str) -> Dict[str, jax.Array]:
        """Row-count + order-independent key checksum, computed per stage."""
        # uint32 modular arithmetic: stable under JAX's default x64-disabled mode.
        k = self.columns[key].astype(jnp.uint32)
        masked = jnp.where(self.valid_bool(), k, jnp.uint32(0))
        return {
            "rows": self.count.astype(jnp.int32),
            "key_sum": masked.sum(dtype=jnp.uint32),
            "key_xor": jnp.bitwise_xor.reduce(masked),
        }

    # -- host-side conveniences ----------------------------------------------
    def to_numpy(self) -> Dict[str, np.ndarray]:
        n = int(self.count)
        idx = np.argsort(~self.valid_numpy(), kind="stable")[:n]
        return {k: np.asarray(v)[idx] for k, v in self.columns.items()}

    def head(self, n: int = 8) -> str:
        data = self.to_numpy()
        names = list(data)
        lines = ["| " + " | ".join(names) + " |"]
        m = min(n, len(next(iter(data.values()))) if data else 0)
        for i in range(m):
            lines.append("| " + " | ".join(str(data[c][i]) for c in names) + " |")
        return "\n".join(lines)
