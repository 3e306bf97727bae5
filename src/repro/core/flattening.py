"""SCALPEL-Flattening: distributed denormalization of star-schema claims data.

The paper's pitch (§3.3): pay the join cost *once* — recursively left-join the
dimension/child tables onto the central fact table, store the result columnar,
and every later query becomes a shuffle-free columnar scan.

TPU adaptation (DESIGN.md §2):
  * Spark shuffle  -> ``jax.lax.all_to_all`` over the mesh ``data`` axis
                      (fixed-capacity hash-partition exchange; XLA needs static
                      shapes so each destination bucket has a capacity and an
                      overflow counter instead of dynamic spill).
  * N:1 join       -> sorted-lookup join (searchsorted + gather).
  * 1:N join       -> offset-expansion join (prefix-sum over match counts);
                      this is what reproduces the PMSI-MCO row blow-up of
                      Table 1 and its block-sparsity discussion in §5.
  * temporal slice -> host-driven loop over time buckets, each bucket a
                      bounded-capacity flatten, results appended (paper: joins
                      "sequentially appended to the output parquet file").
  * monitoring     -> per-stage row counts + key checksums proving no loss.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset as _bs
from repro.core.columnar import (ColumnarTable, NULL_FLOAT, NULL_INT, cumsum,
                                 is_null)
from repro.core.schema import JoinEdge, StarSchema

__all__ = [
    "lookup_join",
    "expand_join",
    "flatten_star",
    "flatten_sliced",
    "FlatteningStats",
    "STAT_FIELDS",
    "stats_from_dict",
    "hash_partition",
    "exchange",
    "distributed_flatten",
]


def _sentinel(dtype) -> jax.Array:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(NULL_FLOAT, dtype)
    return jnp.asarray(NULL_INT, dtype)


def _maxval(dtype) -> jax.Array:
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.finfo(dtype).max, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


@dataclasses.dataclass
class FlatteningStats:
    """Monitoring statistics computed along the flattening (paper §3.3)."""

    stage: str
    rows_in: jax.Array
    rows_out: jax.Array
    matched: jax.Array      # left rows that found >=1 (non-null) right match
    overflow: jax.Array     # rows dropped because a static capacity was hit
    key_sum_in: jax.Array
    key_sum_out: jax.Array
    null_keys: jax.Array = None  # key-is-NULL rows excluded from matching

    def assert_no_loss(self):
        """Host-side check: every input row survived (paper's no-loss audit)."""
        if int(self.overflow) != 0:
            raise AssertionError(f"stage {self.stage}: {int(self.overflow)} rows overflowed")


# Field order of the per-node stats dicts the plan executor emits; mirrors the
# FlatteningStats attributes (minus ``stage``, carried by the node label).
STAT_FIELDS = ("rows_in", "rows_out", "matched", "overflow", "null_keys",
               "key_sum_in", "key_sum_out")


def stats_from_dict(stage: str, d: Mapping[str, jax.Array]) -> FlatteningStats:
    """Rehydrate a FlatteningStats from an executor stats dict."""
    return FlatteningStats(stage=stage, **{k: d[k] for k in STAT_FIELDS})


# ---------------------------------------------------------------------------
# N:1 sorted-lookup join (DCIR block-sparse detail tables, patient repository)
# ---------------------------------------------------------------------------
def lookup_join(
    left: ColumnarTable,
    right: ColumnarTable,
    left_key: str,
    right_key: str,
    prefix: str = "",
) -> Tuple[ColumnarTable, FlatteningStats]:
    """Left join where ``right`` has at most one row per key.

    Right is sorted by key (invalid rows sink with +inf key), left keys are
    located by ``searchsorted``, right attributes gathered, misses filled with
    null sentinels — exactly a hash-lookup join expressed in sorted-columnar
    form (TPUs vastly prefer sorted gathers over scattered hash probes).

    SQL left-join semantics for NULLs: a NULL key never matches anything, so
    null-key right rows are masked out up front (they sink with the invalid
    rows) and null-key left rows miss by construction; both are counted in
    ``FlatteningStats.null_keys``.
    """
    # word-wise validity: every row-mask consumer below gathers its bit
    # straight from the packed words (``bit_at`` fuses into the consumer) —
    # the searchsorted key fills never round-trip validity through a bool
    # column (pinned by the no-unpack tests)
    l_valid = _bs.bit_at(left.valid, jnp.arange(left.capacity, dtype=jnp.int32))
    r_rows = jnp.arange(right.capacity, dtype=jnp.int32)
    r_key_null = is_null(right.columns[right_key]) \
        & _bs.bit_at(right.valid, r_rows)
    right = right.filter(~is_null(right.columns[right_key]))
    r = right.sort_by([right_key])
    cap_r = r.capacity
    lk = left.columns[left_key]
    l_key_null = is_null(lk) & l_valid
    if cap_r == 0:  # empty right table: every left row misses
        pos = jnp.zeros(left.capacity, jnp.int32)
        posc = pos
        found = jnp.zeros(left.capacity, bool)
        r = r.pad_to(1)  # 1-row dummy so gathers below are well-formed
    else:
        rk = jnp.where(_bs.bit_at(r.valid, jnp.arange(cap_r, dtype=jnp.int32)),
                       r.columns[right_key],
                       _maxval(r.columns[right_key].dtype))
        pos = jnp.searchsorted(rk, lk, side="left")
        posc = jnp.clip(pos, 0, cap_r - 1)
        found = ((pos < cap_r) & (rk[posc] == lk) & _bs.bit_at(r.valid, posc)
                 & l_valid & ~is_null(lk))

    new_cols = dict(left.columns)
    for name in r.column_names:
        if name == right_key:
            continue
        out_name = prefix + name
        if out_name in new_cols:
            raise ValueError(f"column collision {out_name!r}; pass a prefix")
        col = r.columns[name]
        new_cols[out_name] = jnp.where(found, col[posc], _sentinel(col.dtype))

    out = ColumnarTable(new_cols, left.valid, left.count, left.capacity)
    key_col = left.columns[left_key].astype(jnp.uint32)
    key_sum = jnp.where(l_valid, key_col, 0).sum(dtype=jnp.uint32)
    stats = FlatteningStats(
        stage=f"lookup_join[{left_key}]",
        rows_in=left.count,
        rows_out=out.count,
        matched=found.sum().astype(jnp.int32),
        overflow=jnp.int32(0),
        key_sum_in=key_sum,
        key_sum_out=key_sum,  # validity unchanged: identical by construction
        null_keys=(l_key_null.sum() + r_key_null.sum()).astype(jnp.int32),
    )
    return out, stats


# ---------------------------------------------------------------------------
# 1:N offset-expansion join (PMSI child tables -> the Table-1 blow-up)
# ---------------------------------------------------------------------------
def expand_join(
    left: ColumnarTable,
    right: ColumnarTable,
    left_key: str,
    right_key: str,
    out_capacity: int,
    prefix: str = "",
) -> Tuple[ColumnarTable, FlatteningStats]:
    """Left join where ``right`` may hold N rows per key; output row per pair.

    Match counts per left row come from two ``searchsorted`` passes over the
    sorted right keys; an exclusive prefix sum turns them into output offsets;
    each output slot locates its (left row, right row) pair by binary search.
    Unmatched left rows still emit one row (left-join semantics) with null
    right attributes.  ``out_capacity`` bounds the static output size; slots
    beyond the true total are invalid, and a positive ``overflow`` statistic
    flags capacity overruns (the audit the paper computes per stage).
    """
    L = left.capacity
    # word-wise validity, as in lookup_join: bits gathered from the packed
    # words at each use site, never expanded to a bool column
    l_valid = _bs.bit_at(left.valid, jnp.arange(L, dtype=jnp.int32))
    r_key_null = is_null(right.columns[right_key]) \
        & _bs.bit_at(right.valid, jnp.arange(right.capacity, dtype=jnp.int32))
    right = right.filter(~is_null(right.columns[right_key]))
    if right.capacity == 0:
        right = right.pad_to(1)
    r = right.sort_by([right_key])
    cap_r = r.capacity
    rk = jnp.where(_bs.bit_at(r.valid, jnp.arange(cap_r, dtype=jnp.int32)),
                   r.columns[right_key], _maxval(r.columns[right_key].dtype))
    lk = left.columns[left_key]
    l_key_null = is_null(lk) & l_valid

    start = jnp.searchsorted(rk, lk, side="left")
    stop = jnp.searchsorted(rk, lk, side="right")
    # NULL keys never match (SQL left-join semantics); null-key left rows
    # still emit one row with null right attributes.
    cnt = jnp.where(l_valid & ~is_null(lk), stop - start, 0)
    out_cnt = jnp.where(l_valid, jnp.maximum(cnt, 1), 0)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), cumsum(out_cnt).astype(jnp.int32)])
    total = offs[-1]

    j = jnp.arange(out_capacity, dtype=jnp.int32)
    src = jnp.clip(jnp.searchsorted(offs, j, side="right") - 1, 0, L - 1)
    rel = j - offs[src]
    has_match = cnt[src] > 0
    ridx = jnp.clip(start[src] + rel, 0, cap_r - 1)
    out_valid = (j < total) & l_valid[src]
    right_ok = has_match & out_valid

    new_cols = {k: jnp.where(out_valid, v[src], _sentinel(v.dtype)) for k, v in left.columns.items()}
    for name in r.column_names:
        if name == right_key:
            continue
        out_name = prefix + name
        if out_name in new_cols:
            raise ValueError(f"column collision {out_name!r}; pass a prefix")
        col = r.columns[name]
        new_cols[out_name] = jnp.where(right_ok, col[ridx], _sentinel(col.dtype))

    out = ColumnarTable(new_cols, out_valid, out_valid.sum().astype(jnp.int32))
    key_u32 = lk.astype(jnp.uint32)
    stats = FlatteningStats(
        stage=f"expand_join[{left_key}]",
        rows_in=left.count,
        rows_out=out.count,
        matched=(cnt > 0).sum().astype(jnp.int32),
        overflow=jnp.maximum(total - out_capacity, 0).astype(jnp.int32),
        key_sum_in=jnp.where(l_valid, key_u32, 0).sum(dtype=jnp.uint32),
        key_sum_out=jnp.where(out_valid, new_cols[left_key].astype(jnp.uint32), 0).sum(dtype=jnp.uint32),
        null_keys=(l_key_null.sum() + r_key_null.sum()).astype(jnp.int32),
    )
    return out, stats


# ---------------------------------------------------------------------------
# Whole-star flattening
# ---------------------------------------------------------------------------
def _run_flatten_plan(plan, out_id, tables):
    """Execute a flattening plan body (traceable) and rehydrate its stats."""
    from repro.study.executor import run_plan_body

    env = {s: tables[s] for s in plan.sources()}
    vals, _, stats = run_plan_body(plan, env, 0, "xla")
    stats_list = [stats_from_dict(plan.nodes[i].label(), stats[i])
                  for i in sorted(stats)]
    return vals[out_id], stats_list


def flatten_star(
    schema: StarSchema,
    tables: Mapping[str, ColumnarTable],
    expand_capacity: Optional[int] = None,
    expand_slack: float = 1.5,
) -> Tuple[ColumnarTable, List[FlatteningStats]]:
    """Denormalize one sub-database: sequential joins from the central table.

    Thin eager wrapper over the plan path (mirrors ``Extractor.__call__``):
    builds the ``scan_star``/join node chain and evaluates it immediately via
    the plan executor's traced body, so it stays jit-able from the outside.
    ``expand_capacity`` bounds each 1:N expansion; when omitted it is derived
    from the static table capacities at trace time.  Studies should instead
    use ``Study.flatten``, whose optimizer pass derives exact capacities from
    table statistics host-side.
    """
    from repro.study.api import contribute_flatten
    from repro.study.plan import PlanBuilder

    b = PlanBuilder()
    out = contribute_flatten(b, schema, expand_capacity=expand_capacity,
                             expand_slack=expand_slack)
    b.set_output("flat", out)
    return _run_flatten_plan(b.build(), out, tables)


def flatten_sliced(
    schema: StarSchema,
    tables: Mapping[str, ColumnarTable],
    time_column: str,
    n_slices: int,
    t0: int,
    t1: int,
    **kw,
) -> Tuple[ColumnarTable, List[FlatteningStats]]:
    """Temporal slicing (paper §3.3): divide the central table by time unit,
    flatten each slice, and append the results — bounds the working set of
    each big join exactly like SCALPEL-Flattening's year/month slicing.

    Host-driven (tables must be concrete, not tracers): the capacity planner
    bounds each slice by its actual row count, so the appended output
    allocates ~sum-of-slice-rows instead of ``n_slices`` copies of the full
    central capacity.
    """
    from repro.study.api import contribute_flatten_sliced
    from repro.study.optimizer import plan_capacities
    from repro.study.plan import PlanBuilder

    b = PlanBuilder()
    out = contribute_flatten_sliced(b, schema, time_column, n_slices, t0, t1,
                                    **kw)
    b.set_output("flat", out)
    plan = plan_capacities(b.build(), tables)
    return _run_flatten_plan(plan, plan.output_ids["flat"], tables)


# ---------------------------------------------------------------------------
# Distributed exchange: the Spark shuffle on the TPU ICI
# ---------------------------------------------------------------------------
def hash_partition(
    table: ColumnarTable, key: str, n_shards: int, per_dest_capacity: int
) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Bucket rows by ``hash(key) % n_shards`` into a fixed send layout.

    Returns ``(send_cols, send_valid, overflow)`` where each send array has
    shape ``(n_shards, per_dest_capacity[, ...])`` ready for ``all_to_all``.
    Rows beyond a destination's capacity are counted in ``overflow`` (they
    would be spilled in Spark; here the capacity is sized with slack and the
    overflow statistic is asserted zero by the monitoring layer).
    """
    cap = table.capacity
    k = table.columns[key].astype(jnp.uint32)
    # Finalizer-style integer hash (splittable, good avalanche) — cheap on VPU.
    h = k * jnp.uint32(0x9E3779B1)
    h = h ^ (h >> 16)
    dest = jnp.where(table.valid_bool(), (h % jnp.uint32(n_shards)).astype(jnp.int32), n_shards)

    order = jnp.argsort(dest, stable=True)           # group rows by destination
    dsort = dest[order]
    group_start = jnp.searchsorted(dsort, jnp.arange(n_shards + 1, dtype=dsort.dtype))
    pos_in_group = jnp.arange(cap, dtype=jnp.int32) - group_start[dsort].astype(jnp.int32)
    ok = (dsort < n_shards) & (pos_in_group < per_dest_capacity)
    oob = n_shards * per_dest_capacity  # scatter target for dropped rows
    slot = jnp.where(ok, dsort * per_dest_capacity + pos_in_group, oob)

    send_valid = (
        jnp.zeros((oob,), bool).at[slot].set(True, mode="drop").reshape(n_shards, per_dest_capacity)
    )
    send_cols = {}
    for name, col in table.columns.items():
        buf = jnp.full((oob,), _sentinel(col.dtype), col.dtype)
        send_cols[name] = buf.at[slot].set(col[order], mode="drop").reshape(
            n_shards, per_dest_capacity
        )
    overflow = ((dsort < n_shards) & ~ok).sum().astype(jnp.int32)
    return send_cols, send_valid, overflow


def exchange(
    table: ColumnarTable, key: str, axis_name: str, n_shards: int, per_dest_capacity: int
) -> Tuple[ColumnarTable, jax.Array]:
    """One shuffle: hash-partition + ``all_to_all`` + local concatenation.

    Must run inside ``shard_map`` over ``axis_name``.  After this call every
    shard holds exactly the rows whose key hashes to it — co-partitioning the
    join inputs the way Spark's exchange does before a sort-merge join.
    """
    send_cols, send_valid, overflow = hash_partition(table, key, n_shards, per_dest_capacity)
    # bool is not a collective-friendly dtype on all backends; move as int8.
    recv_valid = jax.lax.all_to_all(send_valid.astype(jnp.int8), axis_name, 0, 0).astype(bool)
    recv_cols = {n: jax.lax.all_to_all(c, axis_name, 0, 0) for n, c in send_cols.items()}
    out = ColumnarTable(
        {n: c.reshape(-1) for n, c in recv_cols.items()},
        recv_valid.reshape(-1),
        recv_valid.reshape(-1).sum().astype(jnp.int32),
    )
    return out, overflow


def distributed_flatten(
    schema: StarSchema,
    tables: Mapping[str, ColumnarTable],
    mesh: jax.sharding.Mesh,
    axis_name: str = "data",
    slack: float = 2.0,
    min_per_dest: int = 64,
    expand_capacity: Optional[int] = None,
):
    """Multi-shard denormalization: shuffle every table onto the join key,
    then flatten locally — the full SCALPEL-Flattening plan on a mesh.

    Plan (mirrors Spark's physical plan for the paper's §3.3 job):
      1. exchange central + each dimension on their join key (co-partition);
      2. per-shard local joins (lookup/expand);
      3. exchange the flat table on ``patient_id`` so the *output* is
         patient-partitioned — the property that makes every downstream
         extractor collective-free.

    Returns ``(flat_table, overflow_total)``: the flat table is globally
    row-sharded over ``axis_name`` (patient-partitioned), overflow is a
    scalar the caller asserts to be zero.

    Thin wrapper over the plan path: builds the exchange-aware flatten plan
    (``contribute_flatten(exchange=True)`` emits the Spark physical plan —
    exchange both sides of every join onto the join key, then one final
    exchange onto ``patient_id``), lets the optimizer's partitioning-awareness
    pass prune exchanges whose input is already hash-partitioned on the key
    (Spark's EnsureRequirements, formerly a hand-rolled ``flat_pkey`` loop
    here), and executes under ``shard_map`` via ``execute_plan_sharded``.
    """
    from repro.distributed.pipeline import execute_plan_sharded
    from repro.study.api import contribute_flatten
    from repro.study.optimizer import dce, prune_exchanges
    from repro.study.plan import PlanBuilder

    n = mesh.shape[axis_name]
    b = PlanBuilder()
    out = contribute_flatten(b, schema, expand_capacity=expand_capacity,
                             exchange=True, exchange_slack=slack,
                             min_per_dest=min_per_dest)
    b.set_output("flat", out)
    plan = dce(prune_exchanges(b.build(), n_shards=n))
    vals, _, stats = execute_plan_sharded(plan, tables, 0, mesh,
                                          axis_name=axis_name)
    flat = vals[plan.output_ids["flat"]]
    overflow = jnp.int32(sum(s["overflow"] for s in stats.values()))
    return flat, overflow
