"""Pipeline parallelism + sharded study-plan execution.

Part 1 (GPipe): schedule over a "pipe" axis for the model stack.
Part 2 (``execute_plan_sharded``): run a ``repro.study`` Plan shard-local
under ``shard_map`` over patient-partitioned flat tables.

Each mesh stage holds one contiguous block of layers; microbatches stream
through via ``collective_permute`` (the TPU ICI neighbor hop).  The schedule
is the classic GPipe fill-drain: ``M + P - 1`` ticks for M microbatches over
P stages, bubble fraction ``(P-1)/(M+P-1)``.

This is the config-flag feature promised in DESIGN.md §5 — the production
meshes default to DP×TP (+EP/SP); PP composes for >2-pod scale-out where a
"pipe" axis replaces "pod".  Correctness is gated by
``tests/test_pipeline.py`` (pipelined == sequential, fwd and grads).
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe", "pipeline_transformer", "execute_plan_sharded",
           "pad_tables_for_mesh", "place_tables_on_mesh"]


def pad_tables_for_mesh(tables, n_shards: int):
    """Pad table capacities to a multiple of ``32 * n_shards`` so the packed
    uint32 validity words split across the mesh axis exactly on shard row
    boundaries (each shard's word slice is the bitset of its local rows).
    Idempotent — already-padded tables pass through untouched — so resident
    table sets (``study.service``) can pre-pad once at load time."""
    quantum = 32 * int(n_shards)
    out = {}
    for name, t in tables.items():
        cap = -(-t.capacity // quantum) * quantum
        out[name] = t.pad_to(cap) if cap != t.capacity else t
    return out


def place_tables_on_mesh(tables, mesh: Mesh, axis_name: str = "data"):
    """Pad tables to the mesh word quantum and shard their rows over
    ``axis_name``: every column and the packed validity words split into
    contiguous per-device blocks (``NamedSharding(mesh, P(axis))``), the
    same layout the ``shard_map`` bodies read, so a sharded program takes
    them without a reshard.  Scalar counts are replicated.  Arrays already
    in that layout are not copied."""
    from jax.sharding import NamedSharding

    rows = NamedSharding(mesh, P(axis_name))
    whole = NamedSharding(mesh, P())
    tables = pad_tables_for_mesh(tables, mesh.shape[axis_name])
    # leaf-wise: ColumnarTable's pytree round-trip keeps validity packed
    return {k: jax.tree.map(
                lambda x: jax.device_put(x, rows if jnp.ndim(x) else whole), t)
            for k, t in tables.items()}


def gpipe(stage_fn: Callable, mesh: Mesh, n_stages: int, axis_name: str = "pipe"):
    """Build a pipelined apply: ``f(stage_params_stacked, mb_inputs) -> outs``.

    stage_fn(params_one_stage, x_mb) -> y_mb  (same shape as x_mb)
    stage_params_stacked: pytree with leading dim ``n_stages``.
    mb_inputs: (M, mb, ...) microbatches.

    Schedule: at tick t, stage s processes microbatch ``t - s`` (when in
    range); activations hop s -> s+1 between ticks.  Output microbatch m
    leaves the last stage at tick ``m + P - 1``.
    """

    def run(stage_params, mbs):
        M = mbs.shape[0]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def body(params_local, mbs_local):
            # shard_map keeps the sharded stage dim with local extent 1
            params_local = jax.tree.map(lambda a: a[0], params_local)
            stage = jax.lax.axis_index(axis_name)
            buf = jnp.zeros_like(mbs_local[0])
            outs = jnp.zeros_like(mbs_local)
            for t in range(M + n_stages - 1):
                # stage 0 injects microbatch t; others consume the hop buffer
                inject = mbs_local[min(t, M - 1)]
                x_in = jnp.where(stage == 0, inject, buf)
                y = stage_fn(params_local, x_in)
                # microbatch index currently at this stage: t - stage
                mb_idx = t - stage
                # last stage banks its finished microbatch
                is_last = stage == n_stages - 1
                valid = is_last & (mb_idx >= 0) & (mb_idx < M)
                slot = jnp.clip(mb_idx, 0, M - 1)
                outs = jax.lax.cond(
                    valid,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, y, slot, 0),
                    lambda o: o,
                    outs,
                )
                buf = jax.lax.ppermute(y, axis_name, perm)
            # everyone returns outs; only the last stage's is real — broadcast
            # it (one hop ring: psum of masked outs)
            outs = jnp.where(stage == n_stages - 1, outs, 0)
            return jax.lax.psum(outs, axis_name)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(stage_params, mbs)

    return run


def pipeline_transformer(layer_fn: Callable, mesh: Mesh, n_stages: int,
                         axis_name: str = "pipe"):
    """Pipelined stack of identical layers: params stacked (n_stages,
    layers_per_stage, ...); each stage scans its local layers."""

    def stage_fn(stage_params, x):
        def one(x, lp):
            return layer_fn(lp, x), None

        y, _ = jax.lax.scan(one, x, stage_params)
        return y

    return gpipe(stage_fn, mesh, n_stages, axis_name)


# ---------------------------------------------------------------------------
# sharded study-plan execution
# ---------------------------------------------------------------------------
def execute_plan_sharded(plan, tables, n_patients: int, mesh: Mesh,
                         axis_name: str = "data", engine: str = "xla",
                         predicate_engine: str | None = None):
    """Execute a study ``Plan`` shard-local over a mesh ``data`` axis.

    Requirement (same as ``transformers.exposures_sharded``): the flat tables
    are *patient-partitioned* — ``distributed_flatten`` keys its output on
    ``patient_id`` — so every per-patient / per-stay operation (masks, dedupe,
    sorts, transformer folds, subject bitsets) is shard-local and needs no
    collective.  Cross-shard stitches are scalar/bitset ``psum``s only:

      * subject bitsets: each patient lives on exactly one shard, so partial
        bitsets are disjoint and ``psum`` is the bitwise OR;
      * node row counts: local counts sum to the global count.

    Table outputs come back shard-concatenated (each shard's block compacted
    locally, global ``count`` from the psum); they remain valid-masked tables
    like every other plan output.  Returns ``(vals, counts, stats)`` shaped
    like the local executor's so ``Study.run`` shares its realization path —
    ``stats`` holds per-join FlatteningStats as host ints (psum over shards:
    local row counts / overflows / key checksums sum to the global ones).

    Validity is **bitset-sharded**: tables carry packed uint32 validity
    words, so source capacities are padded to a multiple of ``32 * n`` — the
    word array then splits across the mesh axis exactly on shard row
    boundaries, each shard's slice being the packed bitset of its local
    rows — and shard-local table outputs are padded back to a 32-aligned
    capacity before leaving the shard_map so the concatenated global words
    stay row-exact.  Cross-shard subject bitsets and per-node popcounts
    remain scalar/word ``psum``s (disjoint patients: psum == bitwise OR).
    """
    import numpy as np
    from repro.core.bitset import count as _bits_count
    from repro.core.columnar import ColumnarTable
    from repro.study.executor import run_plan_body
    from repro.study.plan import COHORT_OPS, TABLE_OPS

    n = mesh.shape[axis_name]
    env = place_tables_on_mesh({src: tables[src] for src in plan.sources()},
                               mesh, axis_name)
    cols_in = {s: dict(t.columns) for s, t in env.items()}
    valid_in = {s: t.valid for s, t in env.items()}

    out_ids = {i for _, i in plan.outputs}
    table_ids = tuple(i for i in sorted(out_ids)
                      if plan.nodes[i].op in TABLE_OPS)
    # base cohort bitsets cross shards (psum == OR for disjoint patients);
    # interior cohort_op bits stay local — the Study layer replays the
    # algebra on realized operands — but named cohort outputs still export.
    cohort_ids = tuple(i for i, nd in enumerate(plan.nodes)
                       if nd.op == "cohort_from_events"
                       or (nd.op in COHORT_OPS and i in out_ids))
    # event tables feeding cohorts must be realized too (Cohort.events)
    ev_ids = tuple(sorted(set(table_ids) | {
        nd.inputs[0] for nd in plan.nodes if nd.op == "cohort_from_events"}))

    # key on mesh *content* — an id() key could hand a new mesh allocated at
    # a freed mesh's address a stale compiled fn bound to dead devices.
    # Memoized through the executor's shared cache (``cached_executable``),
    # so sharded executables show up in — and reset with — the same
    # ``jit_cache_info()`` compile/hit audit as local ones.
    mesh_key = (tuple(mesh.axis_names),
                tuple(mesh.shape[a] for a in mesh.axis_names),
                tuple(d.id for d in np.ravel(mesh.devices)))
    from repro.kernels.predicate import resolve_engine
    from repro.study.executor import cached_executable

    peng = resolve_engine(predicate_engine, engine)
    key = (plan.key(), n_patients, engine, peng, mesh_key, axis_name)

    def build():
        def body(cols, valids):
            local = {s: ColumnarTable(c, valids[s],
                                      _bits_count(valids[s]))
                     for s, c in cols.items()}
            vals, counts, stats = run_plan_body(
                plan, local, n_patients, engine, axis_name=axis_name,
                n_shards=n, predicate_engine=peng)

            def _aligned(t):
                # 32-align the local capacity so the shard-concatenated
                # validity words stay row-exact on the host side
                cap = -(-t.capacity // 32) * 32
                return t if cap == t.capacity else t.pad_to(cap)

            t_out = {}
            for i in ev_ids:
                t = _aligned(vals[i])
                t_out[i] = (dict(t.columns), t.valid)
            b_out = {i: jax.lax.psum(vals[i], axis_name) for i in cohort_ids}
            # local counts sum to global counts; stacked -> one psum+transfer
            ids = tuple(sorted(counts))
            c_out = jax.lax.psum(jnp.stack([counts[i] for i in ids]), axis_name)
            s_out = jax.lax.psum(stats, axis_name) if stats else {}
            return t_out, b_out, c_out, s_out

        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(), P(), P()), check_vma=False,
        ))

    fn = cached_executable(key, build)
    t_out, b_out, counts_vec, s_out = fn(cols_in, valid_in)
    from repro.study.executor import _host_stats, traced_ids

    counts = {i: int(c) for i, c in
              zip(traced_ids(plan), np.asarray(counts_vec))}
    vals = {i: ColumnarTable(c, v, jnp.int32(counts[i]))
            for i, (c, v) in t_out.items()}
    vals.update(b_out)
    return vals, counts, _host_stats(s_out)
