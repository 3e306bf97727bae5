"""Plan executor: one jit-compiled XLA program per (plan, table spec, engine).

The executor walks a (usually optimizer-rewritten) ``Plan`` and evaluates each
node.  Everything array-valued — scans, masks, dedupe, event conformance,
compaction, cohort bitset algebra, registered transformers — runs inside a
single ``jax.jit`` body, so XLA fuses the shared-scan mask pipelines end to
end; host-side nodes (``featurize``, ``flow``) run after, on realized values.

jit caching: the traced closure is memoized on ``(plan structural key, engine,
n_patients)``; ``jax.jit`` then re-specializes per table spec (shapes/dtypes)
as usual, giving the "plan structure + table spec" cache key for free.

Provenance: the jitted body returns a per-node row/subject count alongside the
outputs, and ``execute`` appends one ``OperationLog`` entry per executed node
— no manual ``log.record`` calls in user code, and flowcharts reconstruct
from the log alone (see ``api.flow_rows_from_log``).
"""
from __future__ import annotations

import inspect
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset as _bs
from repro.core import flattening as _fl
from repro.core import transformers as _tr
from repro.core.cohort import Bitset
from repro.core.columnar import ColumnarTable, is_null
from repro.core.events import make_events
from repro.core.metadata import OperationLog
from repro import tracing
from repro.kernels import predicate as _pk
from repro.study import expr as _expr
from repro.study.plan import (COHORT_OPS, PREDICATE_OPS, Plan, STATS_OPS,
                              TABLE_OPS)

__all__ = ["execute", "TRANSFORMS", "jit_cache_info", "clear_jit_cache",
           "cached_executable"]


# Registered transformer free functions usable from ``transform`` nodes.
# Values are (fn, wants_n_patients); params must stay hashable in the plan.
def _registry() -> Dict[str, Tuple[Callable, bool]]:
    fns = {}
    for name in ("observation_period", "follow_up", "trackloss", "exposures",
                 "fractures", "drug_prescriptions", "drug_interactions",
                 "bladder_cancer", "infarctus", "heart_failure"):
        fn = getattr(_tr, name)
        wants = "n_patients" in inspect.signature(fn).parameters
        fns[name] = (fn, wants)
    return fns


TRANSFORMS = _registry()

_JIT_CACHE: Dict[Tuple, Callable] = {}
_JIT_STATS: Dict[str, int] = {"compiles": 0, "hits": 0}
# the serving layer's realization worker may build/look up executables
# concurrently with the main thread (e.g. featurize replays) — one lock
# guards the cache dict and its counters
_JIT_LOCK = threading.Lock()


def jit_cache_info() -> Dict[str, int]:
    """Cache-surface audit: ``plans`` (live entries), ``compiles`` (traced
    closures built — the executable count the serving layer budgets), and
    ``hits`` (runner lookups served by an existing entry).  Counters reset
    with ``clear_jit_cache``."""
    with _JIT_LOCK:
        return {"plans": len(_JIT_CACHE), **_JIT_STATS}


def clear_jit_cache() -> None:
    with _JIT_LOCK:
        _JIT_CACHE.clear()
        _JIT_STATS["compiles"] = 0
        _JIT_STATS["hits"] = 0


def cached_executable(key: Tuple, build: Callable[[], Callable]) -> Callable:
    """THE process-wide compiled-executable cache: the local jitted runner,
    the sharded ``execute_plan_sharded`` path and the chunked executor all
    memoize through here, so ``jit_cache_info()`` audits every executable in
    the process (and the serving layer's compile budget covers all three
    physical strategies).  ``build`` runs once per distinct ``key``; later
    lookups count as hits."""
    with _JIT_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is None:
            _JIT_STATS["compiles"] += 1
            fn = _JIT_CACHE[key] = build()
        else:
            _JIT_STATS["hits"] += 1
        return fn


# ---------------------------------------------------------------------------
# node evaluation (traced)
# ---------------------------------------------------------------------------
def _compact_table(t: ColumnarTable, engine: str) -> ColumnarTable:
    if engine == "xla":
        return t.compact()
    if engine != "pallas":
        raise ValueError(f"unknown engine {engine!r}")
    from repro.kernels import ops as kops

    cols = {}
    count = None
    for name, col in t.columns.items():
        # packed keep-mask straight into the kernel (1 bit/row of HBM)
        out, cnt = kops.filter_compact(col, t.valid)
        cols[name] = out
        count = cnt if count is None else count
    count = count.astype(jnp.int32)
    return ColumnarTable(cols, _bs.first_n(count, t.capacity), count,
                         t.capacity)


def _stats_dict(fs) -> Dict[str, jax.Array]:
    return {k: getattr(fs, k) for k in _fl.STAT_FIELDS}


def _key_checksum(t: ColumnarTable, key: str) -> jax.Array:
    k = t.columns[key].astype(jnp.uint32)
    return jnp.where(t.valid_bool(), k, 0).sum(dtype=jnp.uint32)


def _eval_node(node, ins, env: Dict[str, ColumnarTable], n_patients: int,
               engine: str, axis_name: Optional[str] = None,
               n_shards: int = 1, predicate_engine: str = "jnp"):
    op = node.op
    if op in ("scan", "scan_star"):
        src = node.get("source")
        if src not in env:
            raise KeyError(f"plan scans source {src!r} but run() got "
                           f"{sorted(env)}")
        return env[src]
    if op == "lookup_join":
        out, fs = _fl.lookup_join(ins[0], ins[1], node.get("left_key"),
                                  node.get("right_key"),
                                  prefix=node.get("prefix") or "")
        return out, _stats_dict(fs)
    if op == "expand_join":
        cap = node.get("capacity")
        if cap is None:
            # trace-time fallback when the host-side capacity planner did not
            # run (e.g. optimize=False, or tables unknown at optimize time)
            cap = int((ins[0].capacity + ins[1].capacity)
                      * (node.get("slack") or 1.5))
        out, fs = _fl.expand_join(ins[0], ins[1], node.get("left_key"),
                                  node.get("right_key"), cap,
                                  prefix=node.get("prefix") or "")
        return out, _stats_dict(fs)
    if op == "exchange":
        t = ins[0]
        key = node.get("key")
        ksum_in = _key_checksum(t, key)
        zero = jnp.int32(0)
        if axis_name is None or n_shards <= 1:
            # off-mesh (or single shard): the shuffle is the identity
            return t, {"rows_in": t.count, "rows_out": t.count,
                       "matched": t.count, "overflow": zero,
                       "null_keys": zero, "key_sum_in": ksum_in,
                       "key_sum_out": ksum_in}
        per = node.get("per_dest_capacity")
        if per is None:
            per = max(int(node.get("min_per_dest") or 64),
                      int(t.capacity * (node.get("slack") or 2.0) / n_shards))
        out, overflow = _fl.exchange(t, key, axis_name, n_shards, per)
        return out, {"rows_in": t.count, "rows_out": out.count,
                     "matched": out.count, "overflow": overflow,
                     "null_keys": zero, "key_sum_in": ksum_in,
                     "key_sum_out": _key_checksum(out, key)}
    if op == "slice_time":
        t = ins[0]
        # the bounds are an Expr like any other predicate (col.between)
        out = t.filter(_expr.node_predicate(node).evaluate(t))
        n_sel = out.count
        ksum_in = _key_checksum(out, node.get("col"))
        cap = node.get("capacity")
        overflow = jnp.int32(0)
        if cap is not None and cap < t.capacity:
            out = _compact_table(out, engine).shrink_to(cap)
            overflow = jnp.maximum(n_sel - cap, 0).astype(jnp.int32)
        return out, {"rows_in": t.count, "rows_out": out.count,
                     "matched": n_sel, "overflow": overflow,
                     "null_keys": jnp.int32(0), "key_sum_in": ksum_in,
                     "key_sum_out": _key_checksum(out, node.get("col"))}
    if op == "key_count":
        # an eliminated (column-pruned) lookup_join: the value is the LEFT
        # table unchanged; the join's no-loss audit survives as a cheap
        # key-membership count over the (pruned-to-key) right side
        left, right = ins
        lk = left.columns[node.get("left_key")]
        lvb = left.valid_bool()
        l_null = is_null(lk) & lvb
        rk_col = right.columns[node.get("right_key")]
        rvb = right.valid_bool()
        r_null = is_null(rk_col) & rvb
        if right.capacity == 0:   # empty right: every key misses (lookup_join
            found = jnp.zeros((left.capacity,), bool)        # has this guard)
        else:
            r_ok = rvb & ~is_null(rk_col)
            rk = jnp.where(r_ok, rk_col, _fl._maxval(rk_col.dtype))
            order = jnp.argsort(rk)
            rs = rk[order]
            pos = jnp.searchsorted(rs, lk, side="left")
            posc = jnp.clip(pos, 0, right.capacity - 1)
            found = ((pos < right.capacity) & (rs[posc] == lk)
                     & r_ok[order][posc] & lvb & ~is_null(lk))
        ksum = jnp.where(lvb, lk.astype(jnp.uint32), 0).sum(dtype=jnp.uint32)
        zero = jnp.int32(0)
        return left, {"rows_in": left.count, "rows_out": left.count,
                      "matched": found.sum().astype(jnp.int32),
                      "overflow": zero,
                      "null_keys": (l_null.sum() + r_null.sum()).astype(jnp.int32),
                      "key_sum_in": ksum, "key_sum_out": ksum}
    if op == "select":
        return ins[0].select(list(node.get("cols")))
    if op in PREDICATE_OPS:
        # every predicate-ish op re-expresses as an Expr; a fused_mask's
        # accumulated conjuncts compile to ONE mask evaluation over the
        # projected columns (expr.fused_predicate).  The node's stamped
        # engine (``assign_engines``) — or the run-level predicate engine —
        # picks between jnp mask algebra and the Pallas Expr->bitset kernel.
        t = ins[0]
        e = _expr.node_predicate(node)
        if e is None:
            return t
        eng = node.get("engine") or predicate_engine
        param = e.to_param()
        if eng == "pallas" and _pk.compilable(param):
            # hoisted slot refs (normalized plans) become kernel operands:
            # the bound (lits, vecs) pair rides along explicitly — the
            # kernel module never reaches back into expr's binding stack
            words, cnt = _pk.predicate_bitset(
                t.columns, t.valid, expr_param=param,
                block=node.get("bitset_block") or _pk.DEFAULT_BLOCK,
                capacity=t.capacity,
                params=_expr.current_bound_params())
            # the kernel's packed words ARE the table's validity — no unpack
            # hop: they flow into cohort_from_events, the cohort bitset
            # algebra and the compaction keep-mask as 1 bit/row metadata
            return ColumnarTable(t.columns, words, cnt, t.capacity)
        mask = e.mask(t)
        return ColumnarTable(t.columns, mask, mask.sum().astype(jnp.int32))
    if op == "dedupe":
        from repro.core.extraction import dedupe_by

        return dedupe_by(ins[0], list(node.get("keys")))
    if op == "conform_events":
        t = ins[0]
        end_col, group_col, weight_col = (node.get("end_col"),
                                          node.get("group_col"),
                                          node.get("weight_col"))
        return make_events(
            patient_id=t.columns["patient_id"],
            category=node.get("category"),
            value=t.columns[node.get("value_col")],
            start=t.columns[node.get("start_col")],
            end=t.columns[end_col] if end_col else None,
            group_id=t.columns[group_col] if group_col else None,
            weight=t.columns[weight_col] if weight_col else None,
            valid=t.valid,
        )
    if op == "compact":
        return _compact_table(ins[0], node.get("engine") or engine)
    if op == "transform":
        fn, wants_np = TRANSFORMS[node.get("fn")]
        kwargs = {k: (list(v) if isinstance(v, tuple) else v)
                  for k, v in (node.get("kwargs") or ())}
        if wants_np:
            kwargs.setdefault("n_patients", n_patients)
        return fn(*ins, **kwargs)
    if op == "concat":
        return ColumnarTable.concat(list(ins))
    if op == "cohort_from_events":
        ev = ins[0]
        return Bitset.from_indices(ev.columns["patient_id"], ev.valid, n_patients)
    if op == "cohort_op":
        a, b = ins
        kind = node.get("kind")
        if engine == "pallas":
            # fused bitwise-op + popcount Pallas kernel (one HBM pass)
            from repro.kernels import ops as kops

            words, _ = kops.bitset_op(
                a, b, {"&": "and", "|": "or", "-": "andnot"}[kind])
            return words
        if kind == "&":
            return a & b
        if kind == "|":
            return a | b
        return a & ~b
    raise ValueError(f"unknown traced op {node.op!r}")


def _node_count(node, val) -> jax.Array:
    if node.op in COHORT_OPS:
        return Bitset.count(val)
    return val.count.astype(jnp.int32)


# ---------------------------------------------------------------------------
# plan-level execution
# ---------------------------------------------------------------------------
def traced_ids(plan: Plan) -> Tuple[int, ...]:
    return tuple(i for i, n in enumerate(plan.nodes)
                 if n.op in TABLE_OPS or n.op in COHORT_OPS)


def keep_ids(plan: Plan) -> Tuple[int, ...]:
    """Node values that must leave the jitted body: named outputs, base
    cohort bitsets, and the event tables cohorts were built from
    (Cohort.events).  Interior ``cohort_op`` bitsets stay internal — the
    Study layer replays the algebra on realized operands, so exporting them
    would be a dead device->host transfer per node.  Everything else stays
    internal so XLA fuses the mask pipelines instead of materializing each
    intermediate into an output buffer."""
    traced = set(traced_ids(plan))
    keep = {i for _, i in plan.outputs if i in traced}
    for i, n in enumerate(plan.nodes):
        if n.op == "cohort_from_events":
            keep.add(i)
            keep.update(j for j in n.inputs if j in traced)
    return tuple(sorted(keep))


def run_plan_body(plan: Plan, env: Dict[str, ColumnarTable], n_patients: int,
                  engine: str, axis_name: Optional[str] = None,
                  n_shards: int = 1, predicate_engine: Optional[str] = None):
    """Pure traced body: node id -> value for every array-valued node, plus
    per-node counts and per-join FlatteningStats dicts.  Reused verbatim by
    ``distributed.pipeline`` under ``shard_map`` (``axis_name``/``n_shards``
    make exchange nodes run real collectives there; off-mesh they are the
    identity).  ``predicate_engine`` is the fallback for predicate nodes the
    optimizer did not stamp (``"auto"``/None resolve by backend)."""
    peng = _pk.resolve_engine(predicate_engine, engine)
    vals: Dict[int, Any] = {}
    counts: Dict[int, jax.Array] = {}
    stats: Dict[int, Dict[str, jax.Array]] = {}
    for i in traced_ids(plan):
        node = plan.nodes[i]
        ins = [vals[j] for j in node.inputs]
        out = _eval_node(node, ins, env, n_patients, engine, axis_name,
                         n_shards, predicate_engine=peng)
        if node.op in STATS_OPS:
            out, stats[i] = out
        vals[i] = out
        counts[i] = _node_count(node, vals[i])
    return vals, counts, stats


def _jitted_runner(plan: Plan, n_patients: int, engine: str,
                   predicate_engine: Optional[str] = None,
                   params_sig: Optional[Tuple] = None) -> Callable:
    peng = _pk.resolve_engine(predicate_engine, engine)
    key = (plan.key(), n_patients, engine, peng, params_sig)

    def build():
        keep = keep_ids(plan)

        def run(env, lits=(), vecs=()):
            # hoisted-literal slots (normalized plans) read the traced
            # lits/vecs arguments; plans with baked literals ignore them
            with _expr.bound_params(lits, vecs):
                vals, counts, stats = run_plan_body(
                    plan, env, n_patients, engine, predicate_engine=peng)
            # counts leave as ONE stacked vector: a single host transfer for
            # provenance instead of one device sync per node.
            ids = tuple(sorted(counts))
            return ({i: vals[i] for i in keep},
                    jnp.stack([counts[i] for i in ids]),
                    stats)

        if params_sig is None:
            def body(env):
                return run(env)
        else:
            def body(env, lits, vecs):
                return run(env, lits, vecs)

        return jax.jit(body)

    return cached_executable(key, build)


def _host_stats(stats) -> Dict[int, Dict[str, int]]:
    return {i: {k: int(np.asarray(v)) for k, v in d.items()}
            for i, d in stats.items()}


def execute(plan: Plan, tables: Dict[str, ColumnarTable], n_patients: int = 0,
            engine: str = "xla", log: Optional[OperationLog] = None,
            jit: bool = True,
            stats_sink: Optional[Dict[int, Dict[str, int]]] = None,
            predicate_engine: Optional[str] = None,
            expr_params: Optional[Tuple[Tuple, Tuple]] = None
            ) -> Dict[int, Any]:
    """Evaluate every array-valued node of ``plan`` over ``tables``.

    Returns {node id: value} for the ``keep_ids`` subset — named outputs,
    cohort bitsets and their source event tables (intermediates never leave
    the compiled program).  Host ops (featurize/flow) are the Study layer's
    job — they need realized Cohort objects (see ``api.Study.run``).
    Per-join ``FlatteningStats`` are recorded into ``log`` automatically and,
    when ``stats_sink`` is given, copied into it as host ints keyed by node
    id.  ``predicate_engine`` ("jnp" | "pallas" | "auto"/None) picks how
    un-stamped predicate nodes evaluate — jnp mask algebra or the Pallas
    Expr->bitset kernel; nodes the optimizer stamped keep their engine.
    ``expr_params`` is the ``(lits, vecs)`` pair backing a *normalized*
    plan's hoisted-literal slots (see ``study.normalize``): the values enter
    the compiled program as traced arguments, so the jit cache keys only on
    their shape/dtype signature — same structure + different literals reuses
    one executable.
    """
    with tracing.span("study.execute"):
        return _execute(plan, tables, n_patients, engine, log, jit,
                        stats_sink, predicate_engine, expr_params)


def _execute(plan, tables, n_patients, engine, log, jit, stats_sink,
             predicate_engine, expr_params) -> Dict[int, Any]:
    missing = [s for s in plan.sources() if s not in tables]
    if missing:
        raise KeyError(f"plan scans source(s) {missing} but run() only got "
                       f"{sorted(tables)}")
    env = {src: tables[src] for src in plan.sources()}
    if jit:
        with tracing.span("execute.dispatch") as s:
            compiles = _JIT_STATS["compiles"]
            if expr_params is None:
                fn, args = _jitted_runner(
                    plan, n_patients, engine, predicate_engine), (env,)
            else:
                from repro.study.normalize import params_signature

                lits, vecs = expr_params
                fn = _jitted_runner(plan, n_patients, engine,
                                    predicate_engine,
                                    params_sig=params_signature(lits, vecs))
                args = (env, tuple(lits), tuple(vecs))
            s.count("compiled", _JIT_STATS["compiles"] > compiles)
            vals, counts_vec, stats = fn(*args)
        with tracing.span("execute.wait") as s:
            # the first read of the program's outputs waits for it to end
            counts_host = np.asarray(counts_vec)
            s.count("host_syncs")
        counts = dict(zip(traced_ids(plan), (int(c) for c in counts_host)))
    else:
        with tracing.span("execute.dispatch"):
            lits, vecs = expr_params or ((), ())
            with _expr.bound_params(lits, vecs):
                vals, counts_dev, stats = run_plan_body(
                    plan, env, n_patients, engine,
                    predicate_engine=predicate_engine)
            vals = {i: vals[i] for i in keep_ids(plan)}
        with tracing.span("execute.wait") as s:
            counts = {i: int(c) for i, c in counts_dev.items()}
            s.count("host_syncs", len(counts))
    if log is not None or stats_sink is not None:
        # host conversion is one blocking transfer per stat scalar — only
        # pay it when someone consumes the stats
        with tracing.span("execute.stats") as s:
            host_stats = _host_stats(stats)
            s.count("host_syncs", sum(len(d) for d in host_stats.values()))
        if log is not None:
            with tracing.span("execute.record"):
                record_plan(plan, counts, log, engine, stats=host_stats,
                            predicate_engine=predicate_engine)
        if stats_sink is not None:
            stats_sink.update(host_stats)
    return vals


def record_plan(plan: Plan, counts: Dict[int, int], log: OperationLog,
                engine: str,
                stats: Optional[Dict[int, Dict[str, int]]] = None,
                predicate_engine: Optional[str] = None) -> None:
    """One OperationLog entry per executed node — automatic provenance.
    ``counts``/``stats`` must already be host ints (see ``execute`` / the
    sharded path in ``distributed.pipeline``: counts cross as one stacked
    vector).  Join/exchange nodes carry their FlatteningStats fields
    (rows_in/out, matched, overflow, null_keys, key checksums) in the entry
    params — the paper's no-loss audit, for free on every flattened study.
    ``predicate_engine`` must match the executing call so un-stamped
    predicate nodes log the engine they actually ran (stamped nodes carry
    their own)."""
    peng = _pk.resolve_engine(predicate_engine, engine)
    out_names = {i: name for name, i in plan.outputs}
    host_counts = {i: int(c) for i, c in counts.items()}

    class _N:  # OperationLog.record introspects ``.count``
        def __init__(self, c):
            self.count = c

    for i, c in host_counts.items():
        node = plan.nodes[i]
        ins = {f"#{j}:{plan.nodes[j].label()}": _N(host_counts[j])
               for j in node.inputs if j in host_counts}
        label = out_names.get(i, node.label())
        params = {}
        for k, v in node.params:
            if k in ("required_columns", "pruned_columns", "cols"):
                params[k] = list(v)          # the column-audit story: record
            elif k == "expr":                # what each stage read, legibly
                params[k] = _expr.render_param(v)
            elif k == "exprs":
                params[k] = [_expr.render_param(e) for e in v]
            elif isinstance(v, (int, float, str, bool, type(None))):
                params[k] = v
            else:
                params[k] = len(v)
        if params.get("engine") is None:
            # nodes the optimizer stamped (predicate engine, explicit compact
            # engine) keep their own; un-stamped predicate nodes log what the
            # executor's fallback actually ran (mirroring _eval_node's
            # compilability check); everything else records the global engine
            if node.op in PREDICATE_OPS:
                e = _expr.node_predicate(node)
                params["engine"] = (
                    "pallas" if peng == "pallas" and e is not None
                    and _pk.compilable(e.to_param()) else "jnp")
            else:
                params["engine"] = engine
        if stats and i in stats:
            params.update(stats[i])
        log.record(op=f"plan:{node.op}:{label}", inputs=ins,
                   outputs={label: _N(c)}, params=params)
