"""Multi-tenant cohort-query service: one resident star schema, many
concurrent Study plans.

SCALPEL3's end state is interactive cohort analysis over a population-scale
claims database — many analysts (tenants) issuing structured cohort queries
against one dataset that stays resident on the accelerator.  The PR 1–5
stack stops at "one Study, one process"; ``CohortQueryService`` adds the
serving layer in three tiers:

1. **Admission + batching** — a ``serving.batching.SlotScheduler``: bounded
   in-flight window (``n_slots``), FIFO-with-priority queueing, per-tenant
   in-flight quotas, bounded queue depth (over-depth submissions are
   *rejected*, not silently dropped).
2. **Plan normalization** (``study.normalize``) — every admitted study's
   optimized plan is canonicalized (stable order, labels stripped, literals
   hoisted into a params vector), so structurally-equal queries from
   different tenants share ONE compiled executable; the literals enter as
   traced arguments.
3. **Cross-tenant subgraph result cache** — each cacheable plan prefix
   (scan/predicate/join subtrees, ``normalize.cut_points``) is
   content-hashed with its literal values resolved back in and keyed by
   table version; a shared scan or predicate bitset is computed once and
   served from the cache for every later query, with LRU eviction under a
   device-byte budget and wholesale invalidation on table-version bump.

Cache injection without recompiles: the compiled program's structure must
not depend on *which* cut nodes hit (that would fork executables per hit
pattern), so each cut node's evaluation is wrapped in ``jax.lax.cond`` over
a traced hit flag — on hit the provided cached table flows through, on miss
the node computes in place.  XLA executes only the taken branch at runtime,
and the flag is a traced scalar, so the hit pattern never retraces.

Async step pipeline: each admitted ticket runs in two stages.  The
*device-submit* stage (optimize, analyze, normalize, program lookup, cache
lookup, dispatch of the compiled program) runs on the calling thread; the
*host-realize* stage (stats transfer, cache insert, ``_finish_result``
replay) runs on a single realization worker, double-buffer style (the same
overlap idiom as ``study.chunked``), so device execution of the next
admitted ticket overlaps host materialization of the previous one.
Scheduler slots release when realization *finishes* — the in-flight window
bounds work actually in flight, not just dispatches.  A submit-stage cache miss
publishes its cut hash in an in-flight registry; a later admission wanting
the same subgraph waits for that realization's insert instead of
recomputing, so pipelined hit/miss accounting matches the synchronous
mode (``ServiceConfig.pipeline=False``) exactly.

Sharded residency: with ``mesh=`` the resident tables are pre-padded to the
mesh word quantum (``distributed.pipeline.pad_tables_for_mesh``) and the
*same* normalization sharing + subgraph cache apply: the compiled program is
a ``shard_map`` body (mirroring ``execute_plan_sharded``'s conventions —
patient-partitioned tables in, psum'd bitsets/counts/stats out) with the
``lax.cond`` hit injection inside, cached cut tables crossing as global
``P(axis)``-sharded operands.  Cache keys and program keys are salted with
the mesh shape + axis so local and sharded entries never collide.  Cut
nodes whose shard-local capacity is not 32-aligned are not injected (their
validity words would straddle shard boundaries); they compute in place.

Results are realized through ``Study._finish_result`` — the exact code path
``Study.run`` uses — so every admitted query's events, cohorts, flowcharts
and features are bit-identical to a solo run of the same study (the
acceptance bar ``benchmarks/serving_bench.py`` gates on).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.columnar import ColumnarTable
from repro.core.metadata import OperationLog
from repro.kernels import predicate as _pk
from repro.serving.batching import SlotScheduler
from repro.study import executor as _executor
# member imports, not `from repro.study import normalize`: the package
# re-exports the normalize() function, shadowing the submodule attribute
from repro.study.normalize import (
    NormalPlan, cut_points, device_params, normalize, params_signature,
    subgraph_hashes,
)
from repro.study.analyze import PlanValidationError, analyze as _analyze_plan
from repro.study.api import Study, StudyResult
from repro.study.expr import bound_params
from repro.study.optimizer import OPTIMIZER_VERSION
from repro.study.plan import COHORT_OPS, Plan, STATS_OPS, TABLE_OPS

__all__ = ["CohortQueryService", "ServiceConfig", "ServiceStats",
           "TenantStats", "QueryTicket"]


# ---------------------------------------------------------------------------
# config / audit surface
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServiceConfig:
    n_slots: int = 8                      # in-flight admission window
    per_tenant_inflight: int = 2          # per-tenant quota within the window
    max_queue: int = 256                  # queue depth; beyond this: reject
    cache_budget_bytes: int = 256 << 20   # subgraph-cache LRU budget
    engine: str = "xla"
    predicate_engine: Optional[str] = None  # None/"auto" resolve by backend
    pipeline: bool = True                 # overlap realize with next submit


@dataclasses.dataclass
class TenantStats:
    submitted: int = 0
    admitted: int = 0
    completed: int = 0
    rejected: int = 0
    failed: int = 0
    invalid: int = 0     # plans rejected by admission-time static analysis
    demoted: int = 0     # predicate nodes normalization demoted pallas->jnp


@dataclasses.dataclass
class ServiceStats:
    """The audit surface: per-tenant admission counts plus cache/compile
    counters.  Mirrored into the service ``OperationLog`` per event."""

    tenants: Dict[str, TenantStats] = dataclasses.field(default_factory=dict)
    queries: int = 0
    compile_count: int = 0            # distinct compiled executables built
    cache_hits: int = 0               # cut subgraphs served from cache
    cache_misses: int = 0             # cut subgraphs computed + inserted
    cache_evictions: int = 0
    cache_entries: int = 0
    cache_bytes: int = 0
    table_version: int = 0
    plans_rejected: int = 0           # error-level static analysis findings
    demotions: int = 0                # pallas->jnp normalization demotions
    submit_s: float = 0.0             # summed device-submit stage time
    realize_s: float = 0.0            # summed host-realize stage time
    wall_s: float = 0.0               # summed drain() wall time

    def tenant(self, name: str) -> TenantStats:
        return self.tenants.setdefault(name, TenantStats())

    def hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    def overlap_s(self) -> float:
        """Wall time saved by the submit/realize pipeline: the summed stage
        times minus the drain wall they actually took (0 when the service
        has only been stepped outside ``drain``)."""
        if not self.wall_s:
            return 0.0
        return max(0.0, self.submit_s + self.realize_s - self.wall_s)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "tenants": {k: dataclasses.asdict(v)
                        for k, v in sorted(self.tenants.items())},
            "queries": self.queries,
            "compile_count": self.compile_count,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_hit_rate": round(self.hit_rate(), 4),
            "cache_evictions": self.cache_evictions,
            "cache_entries": self.cache_entries,
            "cache_bytes": self.cache_bytes,
            "table_version": self.table_version,
            "plans_rejected": self.plans_rejected,
            "demotions": self.demotions,
            "submit_s": round(self.submit_s, 6),
            "realize_s": round(self.realize_s, 6),
            "wall_s": round(self.wall_s, 6),
            "overlap_s": round(self.overlap_s(), 6),
        }


@dataclasses.dataclass
class QueryTicket:
    """One submitted study: filled in as it moves queued -> done/failed.

    ``wire=True`` marks tickets that entered through the declarative wire
    path (``submit_spec``): their failures are always *structured* — any
    exception class maps to ``status == "invalid"`` with ``SPEC-nnn``/
    ``SPnnn`` error codes, and ``wire_payload()`` renders the ticket as the
    service's JSON response (a traceback never reaches a tenant)."""

    tenant: str
    study: Optional[Study]
    priority: int = 0
    seq: int = -1
    status: str = "queued"    # queued | rejected | invalid | done | failed
    result: Optional[StudyResult] = None
    error: Optional[BaseException] = None
    wire: bool = False                # submitted as a spec via the wire path
    cache_hits: int = 0
    cache_misses: int = 0
    compiled: bool = False            # this query built a new executable
    latency_s: float = 0.0            # device-submit start to realized
    submit_s: float = 0.0             # device-submit stage time
    realize_s: float = 0.0            # host-realize stage time
    # the ``service.queued`` span, from submit() to admission: the root of
    # the query's spans
    _queued: Optional[tracing.Span] = dataclasses.field(
        default=None, repr=False, compare=False)
    # in-flight cut registration (see _cut_lookup / _release_cuts)
    _cut_evt: Optional[threading.Event] = dataclasses.field(
        default=None, repr=False, compare=False)
    _cut_hashes: List[str] = dataclasses.field(
        default_factory=list, repr=False, compare=False)

    def wire_payload(self) -> Dict[str, Any]:
        """The ticket as a structured wire response.

        ``done`` -> result summary (event/cohort counts, flow stages, cache
        accounting); ``rejected``/``invalid``/``failed`` -> an ``errors``
        list of ``{code, path|node, message, hint}`` entries
        (``spec.error_payload``).  Exception *types* are mapped to stable
        codes; messages of unexpected exceptions and tracebacks are never
        included."""
        if self.status == "queued":
            return {"status": "queued", "seq": self.seq}
        if self.status == "rejected":
            return {"status": "rejected", "errors": [{
                "code": "SPEC-429",
                "message": "service queue is full; the query was not "
                           "admitted",
                "hint": "resubmit once in-flight queries drain"}]}
        if self.status == "done" and self.result is not None:
            r = self.result
            payload: Dict[str, Any] = {
                "status": "done",
                "events": {k: int(t.count) for k, t in r.events.items()},
                "cohorts": {k: int(c.subject_count())
                            for k, c in r.cohorts.items()},
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compiled": self.compiled,
            }
            if r.flow is not None:
                payload["flow"] = [int(c.subject_count())
                                   for c in r.flow.steps]
            if r.features:
                payload["features"] = sorted(r.features)
            return payload
        from repro.study.spec import error_payload
        err = self.error if self.error is not None \
            else RuntimeError("unresolved ticket")
        return {"status": self.status, "errors": error_payload(err)}


class _Count:
    def __init__(self, c: int) -> None:
        self.count = int(c)


# ---------------------------------------------------------------------------
# compiled shape programs + cache entries
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Program:
    fn: Callable                       # jit(env, lits, vecs, cut_tabs, flags)
    cut_ids: Tuple[int, ...]
    zeros: Dict[int, Any]              # per-cut miss placeholder pytrees


@dataclasses.dataclass
class _CacheEntry:
    value: Any                         # device ColumnarTable (global rows)
    stats: Optional[Dict[str, int]]    # host FlatteningStats (STATS_OPS cuts)
    nbytes: int


def _table_nbytes(t: ColumnarTable) -> int:
    return int(sum(np.dtype(c.dtype).itemsize * int(np.prod(c.shape))
                   for c in t.columns.values())
               + np.dtype(t.valid.dtype).itemsize * int(np.prod(t.valid.shape))
               + 4)


def _zeros_like_struct(struct):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


# ---------------------------------------------------------------------------
# the service
# ---------------------------------------------------------------------------
class CohortQueryService:
    """Admit many tenants' Study plans against one resident table set.

    ``submit`` queues, ``step`` admits one window and dispatches it,
    ``drain`` runs to empty (blocking on in-flight realizations).  With
    ``config.pipeline`` (the default) realization runs on a worker thread so
    the next admission's device work overlaps it; ``pipeline=False`` is the
    synchronous reference mode.  See the module docstring for the
    three-layer architecture.
    """

    def __init__(self, tables: Dict[str, ColumnarTable],
                 table_version: int = 0,
                 config: Optional[ServiceConfig] = None,
                 mesh=None, axis_name: str = "data",
                 log: Optional[OperationLog] = None):
        self.config = config or ServiceConfig()
        self.mesh = mesh
        self.axis_name = axis_name
        self.log = log if log is not None else OperationLog()
        self.stats = ServiceStats(table_version=int(table_version))
        self._version = int(table_version)
        self._env: Dict[str, ColumnarTable] = {}
        self._load_tables(tables)
        self._sched = SlotScheduler(
            self.config.n_slots,
            per_key_quota=self.config.per_tenant_inflight,
            max_queue=self.config.max_queue)
        self._seq = 0
        self._programs: Dict[Tuple, _Program] = {}
        self._cache: "OrderedDict[str, _CacheEntry]" = OrderedDict()
        self._cache_bytes = 0
        # shared mutable state (stats, log, cache, in-flight registry) is
        # touched from the main thread and the realization worker
        self._lock = threading.RLock()
        self._realizer: Optional[ThreadPoolExecutor] = None
        self._pending: "deque[Tuple[QueryTicket, Future]]" = deque()
        self._inflight_cuts: Dict[str, threading.Event] = {}

    @classmethod
    def from_npz_dir(cls, dirpath: str, **kwargs) -> "CohortQueryService":
        """Resident service over a star schema persisted by
        ``data.io.save_star`` (one load per table version)."""
        from repro.data.io import load_star

        return cls(load_star(dirpath), **kwargs)

    # -- residency -----------------------------------------------------------
    def _load_tables(self, tables: Dict[str, ColumnarTable]) -> None:
        # loaded ONCE per table version: device residency is the service's
        # contract — queries never re-upload or reshard sources.  With a mesh
        # the rows shard over the patient axis the programs read them on
        # (leaf-wise device_put: ColumnarTable's pytree round-trip re-packs
        # validity on unflatten)
        if self.mesh is not None:
            from repro.distributed.pipeline import place_tables_on_mesh

            self._env = place_tables_on_mesh(tables, self.mesh,
                                             self.axis_name)
        else:
            self._env = {k: jax.tree.map(jax.device_put, t)
                         for k, t in tables.items()}
        self.log.record(
            op="service:load_tables", inputs={},
            outputs={k: _Count(int(t.count)) for k, t in tables.items()},
            params={"version": self._version,
                    "resident_bytes": sum(_table_nbytes(t)
                                          for t in self._env.values())})

    def update_tables(self, tables: Dict[str, ColumnarTable],
                      version: Optional[int] = None) -> None:
        """Install a new table version: re-residents the star schema, bumps
        the version (invalidating every subgraph-cache entry — the version
        salts the content hashes — and dropping the cached entries' bytes),
        and discards shape programs (table capacities may have changed).
        Quiesces in-flight realizations first: they hold references into the
        outgoing table set."""
        self._quiesce()
        with self._lock:
            self._version = int(version) if version is not None \
                else self._version + 1
            self.stats.table_version = self._version
            dropped = len(self._cache)
            self._cache.clear()
            self._cache_bytes = 0
            self.stats.cache_entries = 0
            self.stats.cache_bytes = 0
            self._programs.clear()
            self._load_tables(tables)
            self.log.record(op="service:update_tables", inputs={},
                            outputs={},
                            params={"version": self._version,
                                    "cache_dropped": dropped})

    # -- admission -----------------------------------------------------------
    def submit(self, study: Study, tenant: str = "default",
               priority: int = 0, wire: bool = False) -> QueryTicket:
        """Queue a study for ``tenant``.  Returns its ticket immediately;
        the ticket resolves during ``step``/``drain``.  Over-depth queues
        reject (``status == "rejected"``)."""
        t = QueryTicket(tenant=tenant, study=study, priority=int(priority),
                        seq=self._seq, wire=wire)
        t._queued = tracing.begin("service.queued", ticket=t.seq,
                                  tenant=tenant)
        self._seq += 1
        with self._lock:
            self.stats.tenant(tenant).submitted += 1
        if not self._sched.submit(t, key=tenant, priority=priority):
            t._queued.attrs["rejected"] = True
            t._queued.end()
            t.status = "rejected"
            with self._lock:
                self.stats.tenant(tenant).rejected += 1
                self.log.record(op=f"service:reject:{tenant}", inputs={},
                                outputs={},
                                params={"queued": self._sched.queued()})
        return t

    def submit_spec(self, spec: Any, tenant: str = "default",
                    priority: int = 0) -> QueryTicket:
        """Queue a declarative wire-format study spec (``study.spec``).

        The spec validates and compiles *before* admission: a malformed
        payload comes back immediately as an ``"invalid"`` ticket carrying
        every ``SPEC-nnn`` finding (and counts into
        ``stats.plans_rejected``), without consuming a queue slot.  A
        compiling spec queues exactly like the equivalent Python-built
        ``Study`` — same optimize -> analyze -> normalize admission, same
        compiled-executable sharing, same subgraph cache, bit-identical
        results — but its ticket is marked ``wire``: every later failure,
        including ``SPnnn`` analyzer rejections and runtime surprises, is
        rendered structurally by ``QueryTicket.wire_payload()``; no
        exception class leaks a traceback to the tenant."""
        from repro.study.spec import compile_spec, error_payload

        try:
            study = compile_spec(spec)
        except Exception as e:  # noqa: BLE001 — wire admission never raises:
            # SpecValidationError carries its SPEC-nnn issues; anything else
            # renders as a single SPEC-900 entry via error_payload.
            t = QueryTicket(tenant=tenant, study=None,
                            priority=int(priority), seq=self._seq, wire=True)
            self._seq += 1
            t.status = "invalid"
            t.error = e
            with self._lock:
                ts = self.stats.tenant(tenant)
                ts.submitted += 1
                ts.invalid += 1
                self.stats.plans_rejected += 1
                self.log.record(
                    op=f"service:invalid:{tenant}", inputs={}, outputs={},
                    params={"errors": [
                        " ".join(str(d.get(k)) for k in
                                 ("code", "node", "path", "message")
                                 if d.get(k) is not None)
                        for d in error_payload(e)][:8]})
            return t
        return self.submit(study, tenant=tenant, priority=priority,
                           wire=True)

    def step(self) -> int:
        """Admit one window of queued tickets (priority order, per-tenant
        quotas) and run their device-submit stage; returns the number
        admitted.  With ``config.pipeline`` the host-realize stage is handed
        to the realization worker and the slot releases when it completes;
        otherwise it runs inline."""
        self._reap(block=False)
        admitted = self._sched.admit()
        for ticket, tenant in admitted:
            ticket._queued.end()
            with self._lock:
                self.stats.tenant(tenant).admitted += 1
            try:
                realize = self._submit_ticket(ticket)
            except Exception as e:  # noqa: BLE001 — isolate tenant failures
                self._resolve_failure(ticket, e)
                self._release_cuts(ticket)
                self._sched.release(tenant)
            else:
                if self.config.pipeline:
                    self._pending.append(
                        (ticket,
                         self._pool().submit(self._realize_ticket, ticket,
                                             realize)))
                else:
                    self._realize_ticket(ticket, realize)
        return len(admitted)

    def drain(self) -> None:
        """Run until the queue is empty and every in-flight realization has
        resolved.  The elapsed wall accrues into ``stats.wall_s`` — the
        baseline the pipeline's ``overlap_s`` accounting is measured
        against."""
        with tracing.span("service.drain") as d:
            while True:
                if self.step():
                    continue
                if self._pending:
                    # nothing admittable: a finishing realization frees slots
                    self._reap(block=True)
                    continue
                break
        with self._lock:
            self.stats.wall_s += d.seconds

    def query(self, study: Study, tenant: str = "default",
              priority: int = 0) -> StudyResult:
        """Submit + drain convenience for single-query callers."""
        t = self.submit(study, tenant=tenant, priority=priority)
        self.drain()
        if t.status == "rejected":
            raise RuntimeError("query rejected: service queue is full")
        if t.error is not None:
            raise t.error
        assert t.result is not None
        return t.result

    # -- pipeline machinery --------------------------------------------------
    def _pool(self) -> ThreadPoolExecutor:
        if self._realizer is None:
            self._realizer = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="svc-realize")
        return self._realizer

    def _reap(self, block: bool) -> int:
        """Pop finished realizations off the pending deque (FIFO — the
        single worker realizes in submission order).  ``block`` waits for
        the oldest one.  Main-thread only."""
        done = 0
        while self._pending and self._pending[0][1].done():
            self._pending.popleft()
            done += 1
        if block and self._pending:
            self._pending[0][1].result()   # _realize_ticket never raises
            self._pending.popleft()
            done += 1
            while self._pending and self._pending[0][1].done():
                self._pending.popleft()
                done += 1
        return done

    def _quiesce(self) -> None:
        while self._pending:
            self._reap(block=True)

    def _realize_ticket(self, ticket: QueryTicket,
                        realize: Callable[[], None]) -> None:
        try:
            realize()
            with self._lock:
                ticket.status = "done"
                self.stats.tenant(ticket.tenant).completed += 1
        except Exception as e:  # noqa: BLE001 — isolate tenant failures
            self._resolve_failure(ticket, e)
        finally:
            self._release_cuts(ticket)
            self._sched.release(ticket.tenant)

    def _resolve_failure(self, ticket: QueryTicket,
                         e: BaseException) -> None:
        """Resolve a ticket whose submit or realize stage threw.

        ``PlanValidationError`` (admission-time static analysis) always maps
        to ``"invalid"`` — it never touched the compile cache, distinct from
        runtime failures.  Wire tickets map *every* exception to
        ``"invalid"`` too: the wire contract is structured rejection with
        stable codes (``QueryTicket.wire_payload``), never a leaked
        traceback, and each counts into ``stats.plans_rejected``.  Python
        tickets keep the legacy ``"failed"`` status with the exception
        re-raisable from ``ticket.error``."""
        invalid = ticket.wire or isinstance(e, PlanValidationError)
        with self._lock:
            ticket.error = e
            ts = self.stats.tenant(ticket.tenant)
            if invalid:
                from repro.study.spec import error_payload

                ticket.status = "invalid"
                ts.invalid += 1
                self.stats.plans_rejected += 1
                self.log.record(
                    op=f"service:invalid:{ticket.tenant}", inputs={},
                    outputs={},
                    params={"errors": [
                        " ".join(str(d.get(k)) for k in
                                 ("code", "node", "path", "message")
                                 if d.get(k) is not None)
                        for d in error_payload(e)][:8]})
            else:
                ticket.status = "failed"
                ts.failed += 1
                self.log.record(op=f"service:failed:{ticket.tenant}",
                                inputs={}, outputs={},
                                params={"error": repr(e)})

    def _release_cuts(self, ticket: QueryTicket) -> None:
        """Retire the ticket's in-flight cut registrations and wake waiters
        (who re-check the cache — on a failed realization the entry is
        absent and the waiter becomes the computer)."""
        evt = ticket._cut_evt
        if evt is None:
            return
        with self._lock:
            for h in ticket._cut_hashes:
                if self._inflight_cuts.get(h) is evt:
                    del self._inflight_cuts[h]
        evt.set()

    # -- execution -----------------------------------------------------------
    def _submit_ticket(self, ticket: QueryTicket) -> Callable[[], None]:
        """Device-submit stage: optimize, admission analysis, normalize,
        program + cache lookup, dispatch.  Returns the host-realize closure
        (run by ``_realize_ticket``, possibly on the worker)."""
        study = ticket.study
        with tracing.span("service.submit", parent=ticket._queued) as sub:
            peng_arg = self.config.predicate_engine
            # a mesh plan keeps its exchanges and per-shard capacities:
            # optimized for one shard, joins would only match rows that
            # happen to share a device
            n_shards = (self.mesh.shape[self.axis_name]
                        if self.mesh is not None else 1)
            plan = study.optimized_plan(tables=self._env, n_shards=n_shards,
                                        predicate_engine=peng_arg or "auto",
                                        engine=self.config.engine)
            # admission-time static analysis: error-level plans (unknown
            # sources, dropped-column reads, provably-empty masks, kind
            # mismatches) are rejected BEFORE they reach normalization or
            # the compile cache — a broken tenant plan must not cost a
            # compile slot or poison shared executables
            diags = _analyze_plan(plan, tables=self._env, n_shards=n_shards,
                                  n_patients=study.n_patients)
            if any(d.severity == "error" for d in diags):
                raise PlanValidationError(diags)
            if self.mesh is not None:
                realize_vals = self._run_sharded(ticket, study, plan)
            else:
                realize_vals = self._run_local(ticket, study, plan)
        ticket.submit_s = sub.seconds
        with self._lock:
            self.stats.submit_s += ticket.submit_s

        def realize() -> None:
            with tracing.span("service.realize", parent=sub) as rs:
                vals, stats_orig, req_log = realize_vals()
                for i, d in stats_orig.items():
                    d.setdefault("stage", plan.nodes[i].label())
                ticket.result = study._finish_result(plan, vals, stats_orig,
                                                     req_log)
            ticket.realize_s = rs.seconds
            ticket.latency_s = (rs.end_ns - sub.start_ns) * 1e-9
            with self._lock:
                self.stats.realize_s += ticket.realize_s
                self.stats.queries += 1
                self.log.record(
                    op=f"service:query:{ticket.tenant}", inputs={},
                    outputs={name: _Count(t.count)
                             for name, t in ticket.result.events.items()},
                    params={"plan_nodes": len(plan.nodes),
                            "cache_hits": ticket.cache_hits,
                            "cache_misses": ticket.cache_misses,
                            "compiled": ticket.compiled,
                            "submit_us": round(ticket.submit_s * 1e6, 1),
                            "realize_us": round(ticket.realize_s * 1e6, 1),
                            "latency_us": round(ticket.latency_s * 1e6, 1)})

        return realize

    def _audit_demotions(self, ticket: QueryTicket,
                         nplan: NormalPlan) -> None:
        if not nplan.demoted:
            return
        # satellite of the engine-feasibility analysis (SP008/SP009): the
        # silent pallas->jnp demotion is auditable — logged per query and
        # counted per tenant.  With hoisted literals now first-class kernel
        # operands this fires only for kernel-infeasible stamps (oversized
        # isin whitelists, non-boolean roots).
        with self._lock:
            self.stats.tenant(ticket.tenant).demoted += len(nplan.demoted)
            self.stats.demotions += len(nplan.demoted)
            self.log.record(
                op=f"service:demote:{ticket.tenant}", inputs={}, outputs={},
                params={"nodes": list(nplan.demoted),
                        "engine": "pallas->jnp",
                        "reason": "kernel-infeasible predicate (oversized "
                                  "isin whitelist or non-boolean root)"})

    def _cut_lookup(self, prog: _Program, hashes: Dict[int, str],
                    ticket: QueryTicket,
                    as_payload: Callable[[_CacheEntry], Any]):
        """Per-cut cache lookup building the injection flags/operands.
        Misses are published in the in-flight registry; a hash another
        ticket is currently realizing is *waited on* (outside the lock) so
        pipelined admissions hit exactly like synchronous ones."""
        if ticket._cut_evt is None:
            ticket._cut_evt = threading.Event()
        flags: Dict[int, Any] = {}
        cut_tabs: Dict[int, Any] = {}
        # entries pinned at lookup time: a later miss's insert may LRU-evict
        # a hit of this very query, but its device value stays referenced
        hit_entries: Dict[int, _CacheEntry] = {}
        for i in prog.cut_ids:
            h = hashes[i]
            while True:
                with self._lock:
                    entry = self._cache.get(h)
                    if entry is not None:
                        self._cache.move_to_end(h)
                        flags[i] = jnp.asarray(True)
                        cut_tabs[i] = as_payload(entry)
                        hit_entries[i] = entry
                        break
                    evt = self._inflight_cuts.get(h)
                    if evt is None or evt is ticket._cut_evt:
                        # we compute it; publish intent for later admissions
                        self._inflight_cuts[h] = ticket._cut_evt
                        if h not in ticket._cut_hashes:
                            ticket._cut_hashes.append(h)
                        flags[i] = jnp.asarray(False)
                        cut_tabs[i] = prog.zeros[i]
                        break
                # an earlier ticket is realizing this subgraph: wait for its
                # insert, then re-check (it may have failed -> we compute)
                evt.wait()
        return flags, cut_tabs, hit_entries

    def _run_local(self, ticket: QueryTicket, study: Study, plan: Plan):
        """Normalize -> shared executable -> subgraph cache; returns the
        realize closure mapping canonical values back to the original
        plan's node ids."""
        peng = _pk.resolve_engine(self.config.predicate_engine,
                                  self.config.engine)
        nplan = normalize(plan)
        self._audit_demotions(ticket, nplan)
        lits, vecs = device_params(nplan)
        env = {s: self._env[s] for s in nplan.plan.sources()}
        prog = self._program(ticket, nplan, study.n_patients, peng, env,
                             lits, vecs)

        salt = (self._version, study.n_patients, self.config.engine, peng,
                OPTIMIZER_VERSION)
        hashes = subgraph_hashes(nplan, salt=salt)
        flags, cut_tabs, hit_entries = self._cut_lookup(
            prog, hashes, ticket, lambda e: e.value)

        keep_vals, cut_vals, stats = prog.fn(env, lits, vecs, cut_tabs, flags)

        def realize_vals():
            host_stats = _executor._host_stats(stats)
            with self._lock:
                for i in prog.cut_ids:
                    if i in hit_entries:
                        ticket.cache_hits += 1
                        self.stats.cache_hits += 1
                        if hit_entries[i].stats is not None:
                            host_stats[i] = dict(hit_entries[i].stats)
                    else:
                        ticket.cache_misses += 1
                        self.stats.cache_misses += 1
                        self._insert(hashes[i], cut_vals[i],
                                     host_stats.get(i))

            # canonical ids -> original ids (many-to-one, canonical side)
            vals = {}
            stats_orig: Dict[int, Dict[str, int]] = {}
            canon_of = nplan.orig_to_canon()
            keep_orig = _executor.keep_ids(plan)
            for oi in range(len(plan.nodes)):
                ci = canon_of.get(oi)
                if ci is None:
                    continue
                if oi in keep_orig and ci in keep_vals:
                    vals[oi] = keep_vals[ci]
                if ci in host_stats:
                    stats_orig[oi] = dict(host_stats[ci])
            return vals, stats_orig, OperationLog()

        return realize_vals

    def _run_sharded(self, ticket: QueryTicket, study: Study, plan: Plan):
        """The sharded twin of ``_run_local``: same normalization sharing
        and subgraph cache, program body under ``shard_map`` (conventions
        mirrored from ``distributed.pipeline.execute_plan_sharded``)."""
        peng = _pk.resolve_engine(self.config.predicate_engine,
                                  self.config.engine)
        nplan = normalize(plan)
        self._audit_demotions(ticket, nplan)
        lits, vecs = device_params(nplan)
        env = {s: self._env[s] for s in nplan.plan.sources()}
        prog = self._program(ticket, nplan, study.n_patients, peng, env,
                             lits, vecs)

        salt = (self._version, study.n_patients, self.config.engine, peng,
                OPTIMIZER_VERSION, self._mesh_key(), self.axis_name)
        hashes = subgraph_hashes(nplan, salt=salt)
        flags, cut_tabs, hit_entries = self._cut_lookup(
            prog, hashes, ticket,
            lambda e: (dict(e.value.columns), e.value.valid))

        cols_in = {s: dict(t.columns) for s, t in env.items()}
        valid_in = {s: t.valid for s, t in env.items()}
        t_out, b_out, counts_vec, s_out, cut_out = prog.fn(
            cols_in, valid_in, lits, vecs, cut_tabs, flags)
        cplan = nplan.plan

        def realize_vals():
            counts_c = {i: int(c) for i, c in
                        zip(_executor.traced_ids(cplan),
                            np.asarray(counts_vec))}
            host_stats = _executor._host_stats(s_out)
            with self._lock:
                for i in prog.cut_ids:
                    if i in hit_entries:
                        ticket.cache_hits += 1
                        self.stats.cache_hits += 1
                        if hit_entries[i].stats is not None:
                            host_stats[i] = dict(hit_entries[i].stats)
                    else:
                        ticket.cache_misses += 1
                        self.stats.cache_misses += 1
                        c, v = cut_out[i]
                        self._insert(
                            hashes[i],
                            ColumnarTable(c, v, jnp.int32(counts_c[i])),
                            host_stats.get(i))

            vals_c: Dict[int, Any] = {
                i: ColumnarTable(c, v, jnp.int32(counts_c[i]))
                for i, (c, v) in t_out.items()}
            vals_c.update(b_out)
            canon_of = nplan.orig_to_canon()
            vals: Dict[int, Any] = {}
            counts: Dict[int, int] = {}
            stats_orig: Dict[int, Dict[str, int]] = {}
            for oi in range(len(plan.nodes)):
                ci = canon_of.get(oi)
                if ci is None:
                    continue
                if ci in vals_c:
                    vals[oi] = vals_c[ci]
                if ci in counts_c:
                    counts[oi] = counts_c[ci]
                if ci in host_stats:
                    stats_orig[oi] = dict(host_stats[ci])
            req_log = OperationLog()
            _executor.record_plan(
                plan, counts, req_log, self.config.engine, stats=stats_orig,
                predicate_engine=self.config.predicate_engine)
            return vals, stats_orig, req_log

        return realize_vals

    # -- compiled shape programs --------------------------------------------
    def _mesh_key(self) -> Tuple:
        m = self.mesh
        return (tuple(m.axis_names),
                tuple(m.shape[a] for a in m.axis_names),
                tuple(d.id for d in np.ravel(m.devices)))

    def _program(self, ticket: QueryTicket, nplan: NormalPlan,
                 n_patients: int, peng: str, env, lits, vecs) -> _Program:
        skey = (nplan.plan.key(), n_patients, self.config.engine, peng,
                params_signature(lits, vecs))
        if self.mesh is not None:
            skey += (self._mesh_key(), self.axis_name)
        prog = self._programs.get(skey)
        if prog is not None:
            return prog
        if self.mesh is not None:
            prog = self._build_sharded_program(nplan, n_patients, peng, env,
                                               lits, vecs)
        else:
            prog = self._build_local_program(nplan, n_patients, peng, env,
                                             lits, vecs)
        self._programs[skey] = prog
        with self._lock:
            self.stats.compile_count += 1
            ticket.compiled = True
            self.log.record(op="service:compile", inputs={}, outputs={},
                            params={"plan_nodes": len(nplan.plan.nodes),
                                    "cut_points": len(prog.cut_ids),
                                    "sharded": self.mesh is not None,
                                    "executables": self.stats.compile_count})
        return prog

    def _build_local_program(self, nplan: NormalPlan, n_patients: int,
                             peng: str, env, lits, vecs) -> _Program:
        plan = nplan.plan
        engine = self.config.engine
        cut_ids = cut_points(plan)
        cut_set = frozenset(cut_ids)
        keep = _executor.keep_ids(plan)
        traced = _executor.traced_ids(plan)

        def _cut_structs(env, lits, vecs):
            with bound_params(lits, vecs):
                vals, _, stats = _executor.run_plan_body(
                    plan, env, n_patients, engine, predicate_engine=peng)
            return {i: (vals[i], stats.get(i)) for i in cut_ids}

        struct = jax.eval_shape(_cut_structs, env, lits, vecs)

        def body(env, lits, vecs, cut_tabs, flags):
            with bound_params(lits, vecs):
                vals: Dict[int, Any] = {}
                stats: Dict[int, Any] = {}
                for i in traced:
                    node = plan.nodes[i]
                    ins = [vals[j] for j in node.inputs]
                    if i in cut_set:
                        # structure-stable cache injection: the cond picks
                        # between the cached table and computing in place,
                        # so the executable is identical whatever hits
                        def _compute(node=node, ins=ins):
                            out = _executor._eval_node(
                                node, ins, env, n_patients, engine,
                                predicate_engine=peng)
                            if node.op in STATS_OPS:
                                return out
                            return (out, None)

                        def _cached(i=i):
                            st = struct[i][1]
                            return (cut_tabs[i],
                                    None if st is None
                                    else _zeros_like_struct(st))

                        out, st = jax.lax.cond(flags[i], _cached, _compute)
                        if st is not None:
                            stats[i] = st
                    else:
                        out = _executor._eval_node(
                            node, ins, env, n_patients, engine,
                            predicate_engine=peng)
                        if node.op in STATS_OPS:
                            out, stats[i] = out
                    vals[i] = out
                return ({i: vals[i] for i in keep},
                        {i: vals[i] for i in cut_ids},
                        stats)

        return _Program(fn=jax.jit(body), cut_ids=cut_ids,
                        zeros={i: _zeros_like_struct(struct[i][0])
                               for i in cut_ids})

    def _build_sharded_program(self, nplan: NormalPlan, n_patients: int,
                               peng: str, env, lits, vecs) -> _Program:
        """Compile the normalized plan as ONE shard_map body with the
        lax.cond cache injection inside.  Export conventions mirror
        ``execute_plan_sharded``: tables cross the boundary as
        ``(columns, valid)`` tuples under ``P(axis)``, cohort bitsets /
        stacked counts / join stats psum out replicated.  Injection-eligible
        cut nodes are those whose shard-local capacity is 32-aligned (the
        cached global words then split on shard row boundaries); the rest
        compute in place, uncached."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.bitset import count as _bits_count

        plan = nplan.plan
        mesh, axis = self.mesh, self.axis_name
        n = mesh.shape[axis]
        engine = self.config.engine
        out_ids = {i for _, i in plan.outputs}
        table_ids = tuple(i for i in sorted(out_ids)
                          if plan.nodes[i].op in TABLE_OPS)
        cohort_ids = tuple(i for i, nd in enumerate(plan.nodes)
                           if nd.op == "cohort_from_events"
                           or (nd.op in COHORT_OPS and i in out_ids))
        ev_ids = tuple(sorted(set(table_ids) | {
            nd.inputs[0] for nd in plan.nodes
            if nd.op == "cohort_from_events"}))
        candidates = cut_points(plan)
        traced = _executor.traced_ids(plan)
        cols_in = {s: dict(t.columns) for s, t in env.items()}
        valid_in = {s: t.valid for s, t in env.items()}

        def _aligned(t):
            # 32-align the local capacity so the shard-concatenated
            # validity words stay row-exact on the host side
            cap = -(-t.capacity // 32) * 32
            return t if cap == t.capacity else t.pad_to(cap)

        def probe(cols, valids, lits, vecs):
            local = {s: ColumnarTable(c, valids[s], _bits_count(valids[s]))
                     for s, c in cols.items()}
            with bound_params(lits, vecs):
                vals, _, stats = _executor.run_plan_body(
                    plan, local, n_patients, engine, axis_name=axis,
                    n_shards=n, predicate_engine=peng)
            return ({i: (dict(vals[i].columns), vals[i].valid)
                     for i in candidates},
                    {i: vals[i].count for i in candidates},
                    {i: stats.get(i) for i in candidates})

        probe_fn = jax.shard_map(
            probe, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=(P(axis), P(), P()), check_vma=False)
        cut_struct, cnt_struct, stats_struct = jax.eval_shape(
            probe_fn, cols_in, valid_in, lits, vecs)

        def _eligible(i) -> bool:
            cs, valid = cut_struct[i]
            if not cs:
                return False       # no column to read the capacity from
            rows = next(iter(cs.values())).shape[0]
            return (rows // n) % 32 == 0 and valid.shape[0] * 32 == rows

        cut_ids = tuple(i for i in candidates if _eligible(i))
        cut_set = frozenset(cut_ids)
        # miss placeholders live where the program reads cut tables: sharded
        # over the patient axis, so a miss moves no bytes between devices
        rows = NamedSharding(mesh, P(axis))
        zeros = {i: jax.tree.map(
                     lambda s: jnp.zeros(s.shape, s.dtype, device=rows),
                     cut_struct[i])
                 for i in cut_ids}

        def body(cols, valids, lits, vecs, cut_tabs, flags):
            local = {s: ColumnarTable(c, valids[s], _bits_count(valids[s]))
                     for s, c in cols.items()}
            with bound_params(lits, vecs):
                vals: Dict[int, Any] = {}
                counts: Dict[int, Any] = {}
                stats: Dict[int, Any] = {}
                for i in traced:
                    node = plan.nodes[i]
                    ins = [vals[j] for j in node.inputs]
                    if i in cut_set:
                        def _compute(node=node, ins=ins):
                            out = _executor._eval_node(
                                node, ins, local, n_patients, engine, axis,
                                n, predicate_engine=peng)
                            if node.op in STATS_OPS:
                                return out
                            return (out, None)

                        def _cached(i=i):
                            c, v = cut_tabs[i]
                            cnt = _bits_count(v).astype(cnt_struct[i].dtype)
                            st = stats_struct[i]
                            return (ColumnarTable(c, v, cnt),
                                    None if st is None
                                    else _zeros_like_struct(st))

                        out, st = jax.lax.cond(flags[i], _cached, _compute)
                        if st is not None:
                            stats[i] = st
                    else:
                        out = _executor._eval_node(
                            node, ins, local, n_patients, engine, axis, n,
                            predicate_engine=peng)
                        if node.op in STATS_OPS:
                            out, stats[i] = out
                    vals[i] = out
                    counts[i] = _executor._node_count(node, out)
            t_out = {}
            for i in ev_ids:
                t = _aligned(vals[i])
                t_out[i] = (dict(t.columns), t.valid)
            # eligible cuts are already 32-aligned: export as computed
            cut_out = {i: (dict(vals[i].columns), vals[i].valid)
                       for i in cut_ids}
            b_out = {i: jax.lax.psum(vals[i], axis) for i in cohort_ids}
            ids = tuple(sorted(counts))
            c_out = jax.lax.psum(jnp.stack([counts[i] for i in ids]), axis)
            s_out = jax.lax.psum(stats, axis) if stats else {}
            return t_out, b_out, c_out, s_out, cut_out

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P(), P(axis), P()),
            out_specs=(P(axis), P(), P(), P(), P(axis)), check_vma=False))
        return _Program(fn=fn, cut_ids=cut_ids, zeros=zeros)

    # -- subgraph cache ------------------------------------------------------
    def _insert(self, h: str, value: Any,
                stats: Optional[Dict[str, int]]) -> None:
        """Insert under the service lock (callers hold it).  Idempotent: a
        duplicate hash replaces the old entry without double-counting."""
        nbytes = _table_nbytes(value)
        if nbytes > self.config.cache_budget_bytes:
            return                      # larger than the whole budget: skip
        old = self._cache.pop(h, None)
        if old is not None:
            self._cache_bytes -= old.nbytes
        self._cache[h] = _CacheEntry(value=value, stats=stats, nbytes=nbytes)
        self._cache_bytes += nbytes
        while self._cache_bytes > self.config.cache_budget_bytes:
            _, old = self._cache.popitem(last=False)   # LRU eviction
            self._cache_bytes -= old.nbytes
            self.stats.cache_evictions += 1
            self.log.record(op="service:evict", inputs={}, outputs={},
                            params={"freed_bytes": old.nbytes,
                                    "cache_bytes": self._cache_bytes})
        self.stats.cache_entries = len(self._cache)
        self.stats.cache_bytes = self._cache_bytes
