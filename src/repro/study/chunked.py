"""Out-of-core chunked plan execution — streaming a ``ChunkStore`` through
the resident executor with double-buffered prefetch and resumable
checkpoints.

The paper's headline run (15e9 events, ~15 TB, 49 minutes) cannot be
device-resident; this module is the physical strategy that retargets an
unchanged logical Study plan onto a partitioned star (PolyFrame's
one-logical-plan / many-physical-plans seam, Conquery's partitioned-storage
scan).  The pieces:

* **One executable for all chunks.**  Every chunk has the same fixed
  capacity, so per-chunk tables are pytree-identical in shape/dtype and the
  executor's jit cache serves chunk 2..N from the chunk-1 compile.  Plans
  whose join capacities are content-dependent are capacity-planned per
  chunk and the stamped capacities merged to the elementwise max
  (``_merge_capacity_plans``) — one conservative executable instead of one
  compile per chunk.
* **Double-buffered prefetch.**  A one-worker thread pool loads chunk i+1
  from disk (mmap/decompress, the GIL-released part) and stages it onto the
  device while the jitted program for chunk i runs — the classic
  load/execute overlap; measured and gated by ``benchmarks/chunked_bench``.
* **Exact merge.**  Chunk-dependent table outputs concatenate in chunk
  order (row-local plan ops preserve per-chunk row order, so the valid rows
  of the concat ARE the resident path's valid rows, in order); cohort
  bitsets OR together (has-any-event membership is a union over the
  patient's chunks); FlatteningStats fields sum (uint32 key checksums are
  modular); chunk-independent branches (resident dimension lineage) are
  taken from one chunk instead of summed N times; interior cohort-algebra
  counts are replayed host-side over the merged words so provenance is
  exact, not a sum of per-chunk popcounts.  Plan-level ``concat`` outputs
  get a *branch-aware* merge: the resident path emits [branch1; branch2]
  while each chunk emits its own [branch1_ci; branch2_ci], so naive
  chunk-order concatenation would interleave the branches — instead each
  chunk's concat table is sliced back into its branch windows (boundaries
  read off ``jax.eval_shape`` of the plan body; capacities are 32-row
  aligned so validity slices word-wise) and reassembled branch-major.
* **Checkpoint journal.**  With ``checkpoint_dir`` set, each completed
  chunk spills its kept values via ``data/io.py`` and appends a journal
  line (fsync'd); a killed run re-opens the journal, verifies the plan/
  store stamp, loads the spilled partial state and executes only the
  remaining chunks (see ``tests/test_chunked.py`` kill-and-resume battery).

Soundness guard: ``transform`` (per-patient folds) and ``dedupe`` nodes
downstream of the chunked scan see only one chunk's rows at a time — a
patient's events may span chunks, so per-chunk evaluation + concat is NOT
the resident semantics.  Such plans are rejected with a clear error
(``allow_unsafe=True`` opts out, documented as approximate).  The static
analyzer additionally rejects misaligned chunk capacities (SP015) before
any IO happens.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.columnar import ColumnarTable
from repro.core.metadata import OperationLog
from repro.data.chunkstore import ChunkStore
from repro.data.io import load_columnar_arrays, save_columnar_arrays
from repro.study import executor as _executor
from repro.study import optimizer as _optimizer
from repro.study.plan import Node, Plan

__all__ = ["ChunkedExecutor", "ChunkedReport", "chunk_dependent_ids",
           "chunk_unsafe_ops"]

JOURNAL_NAME = "journal.jsonl"

# ops whose per-chunk evaluation differs from whole-table evaluation when a
# patient's rows span a chunk boundary (cross-row folds / cross-row dedupe)
CHUNK_UNSAFE_OPS = ("transform", "dedupe")


def _fsync_dir(path: str) -> None:
    """Durably record directory entries (the renamed meta.json) — best
    effort on platforms whose directories cannot be opened for fsync."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def chunk_dependent_ids(plan: Plan, source: str) -> Set[int]:
    """Node ids whose value depends on the chunked ``source`` — everything
    reachable from its scans.  Complement = resident lineage (dimension
    branches), computed once and merged by reference, not summed N times."""
    dep: Set[int] = set()
    for i, n in enumerate(plan.nodes):
        if n.op in ("scan", "scan_star") and n.get("source") == source:
            dep.add(i)
        elif any(j in dep for j in n.inputs):
            dep.add(i)
    return dep


def chunk_unsafe_ops(plan: Plan, source: str) -> List[Tuple[int, str]]:
    """(node id, op) for every chunk-unsafe op downstream of the chunked
    scan (see module docstring)."""
    dep = chunk_dependent_ids(plan, source)
    return [(i, plan.nodes[i].op) for i in sorted(dep)
            if plan.nodes[i].op in CHUNK_UNSAFE_OPS]


def _unwrap_compacted_concats(plan: Plan, dep: Set[int]) -> Plan:
    """Retarget named outputs that are compact wrappers over chunk-dependent
    concats at the concat node itself.  Each chunk's compact squeezes ITS
    OWN branch rows together, so the dense layout's branch boundaries are
    dynamic and the merge could not slice branches back apart; the raw
    concat's branch windows are static (trace-time capacities) and its
    valid-row contents are identical — compaction only drops padding."""
    new_out = []
    changed = False
    for name, nid in plan.outputs:
        tgt = nid
        while plan.nodes[tgt].op == "compact":
            tgt = plan.nodes[tgt].inputs[0]
        if (tgt != nid and tgt in dep and plan.nodes[tgt].op == "concat"
                and len(plan.nodes[tgt].inputs) > 1):
            new_out.append((name, tgt))
            changed = True
        else:
            new_out.append((name, nid))
    return dataclasses.replace(plan, outputs=tuple(new_out)) if changed \
        else plan


def _concat_probe_ids(plan: Plan, nid: int, dep: Set[int]) -> Set[int]:
    """Node ids whose padded row counts the branch-aware concat merge needs:
    every input reachable through nested chunk-dependent concats."""
    out: Set[int] = set()
    stack = [nid]
    while stack:
        for k in plan.nodes[stack.pop()].inputs:
            out.add(k)
            if plan.nodes[k].op == "concat" and k in dep:
                stack.append(k)
    return out


def _padded_rows(plan: Plan, env: Dict[str, ColumnarTable], n_patients: int,
                 engine: str, predicate_engine: Optional[str],
                 nids: List[int]) -> Dict[int, int]:
    """Padded (capacity) row counts of table nodes ``nids`` under the
    per-chunk env — shapes only, via ``jax.eval_shape``: no FLOPs, no
    transfers, and identical for every chunk (one executable ⇒ pytree-
    identical shapes)."""
    def body(e):
        vals, _, _ = _executor.run_plan_body(
            plan, e, n_patients, engine, predicate_engine=predicate_engine)
        return {i: vals[i].valid for i in nids}
    words = jax.eval_shape(body, env)
    return {i: int(w.shape[0]) * 32 for i, w in words.items()}


def _concat_windows(plan: Plan, nid: int, dep: Set[int],
                    rows_of: Dict[int, int], off: int = 0
                    ) -> List[Tuple[int, int, int]]:
    """Resident-ordered ``(node, start, stop)`` padded-row windows of a
    concat node's branches inside its per-chunk output table, recursing
    through nested chunk-dependent concats so a concat-of-concats flattens
    to the same leaf order the resident path materializes."""
    out: List[Tuple[int, int, int]] = []
    for k in plan.nodes[nid].inputs:
        if plan.nodes[k].op == "concat" and k in dep:
            out.extend(_concat_windows(plan, k, dep, rows_of, off))
        else:
            out.append((k, off, off + rows_of[k]))
        off += rows_of[k]
    return out


def _slice_rows(t: ColumnarTable, a: int, b: int) -> ColumnarTable:
    """Padded-row window [a, b) of a table.  Capacities are 32-row aligned
    end to end, so the validity bitset slices word-wise — no repacking."""
    if a % 32 or b % 32:
        raise RuntimeError(
            f"concat branch window [{a}, {b}) is not 32-row aligned")
    cols = {c: v[a:b] for c, v in t.columns.items()}
    return ColumnarTable.from_columns(cols, valid=t.valid[a // 32: b // 32])


def _merge_capacity_plans(plans: List[Plan]) -> Plan:
    """Merge per-chunk capacity-planned plans into one: identical structure
    required; ``capacity``/``per_dest_capacity`` params take the max across
    chunks so ONE executable holds every chunk's rows."""
    base = plans[0]
    if any(p.outputs != base.outputs or len(p.nodes) != len(base.nodes)
           for p in plans[1:]):
        raise ValueError("per-chunk optimized plans diverged structurally; "
                         "cannot share one executable")
    nodes = []
    for idx, n0 in enumerate(base.nodes):
        variants = [p.nodes[idx] for p in plans]
        if all(v == n0 for v in variants[1:]):
            nodes.append(n0)
            continue
        keys = [k for k, _ in n0.params]
        if any(v.op != n0.op or v.inputs != n0.inputs
               or [k for k, _ in v.params] != keys for v in variants[1:]):
            raise ValueError(f"per-chunk plans diverged at node {idx} "
                             f"({n0.op}) beyond planned capacities")
        params = []
        for k in keys:
            vals = [v.get(k) for v in variants]
            if all(v == vals[0] for v in vals[1:]):
                params.append((k, vals[0]))
            elif k in ("capacity", "per_dest_capacity") and all(
                    isinstance(v, int) for v in vals):
                params.append((k, max(vals)))
            else:
                raise ValueError(f"per-chunk plans disagree on param {k!r} "
                                 f"of node {idx} ({n0.op}); only planned "
                                 "capacities may vary across chunks")
        nodes.append(Node(n0.op, n0.inputs, tuple(params)))
    return Plan(tuple(nodes), base.outputs)


def _sum_stats(acc: Dict[str, int], d: Dict[str, int]) -> Dict[str, int]:
    out = dict(acc)
    for k, v in d.items():
        s = out.get(k, 0) + int(v)
        if k.startswith("key_sum"):
            s &= 0xFFFFFFFF          # uint32 modular checksum
        out[k] = s
    return out


def _replay_cohort_counts(plan: Plan, base_bits: Dict[int, np.ndarray]
                          ) -> Dict[int, int]:
    """Exact merged counts for EVERY cohort node: replay the bitset algebra
    host-side over the merged base words (summing per-chunk popcounts of an
    intersection would overcount patients present in several chunks)."""
    words: Dict[int, np.ndarray] = {}
    counts: Dict[int, int] = {}
    for i, n in enumerate(plan.nodes):
        if n.op == "cohort_from_events":
            words[i] = base_bits[i]
        elif n.op == "cohort_op":
            a, b = (words[j] for j in n.inputs)
            kind = n.get("kind")
            words[i] = (a & b if kind == "&" else
                        a | b if kind == "|" else a & ~b)
        else:
            continue
        counts[i] = int(np.bitwise_count(words[i]).sum())
    return counts


@dataclasses.dataclass
class ChunkedReport:
    """Timing/audit facts of one chunked run (the bench gate's evidence)."""

    n_chunks: int = 0
    executed: int = 0                # chunks run in this process
    resumed: int = 0                 # chunks restored from the journal
    compiles: int = 0                # executor compiles during the run (==1)
    load_s: float = 0.0              # sum of host load + device staging
    exec_s: float = 0.0              # sum of on-device execution
    wall_s: float = 0.0              # pipelined wall clock of the loop
    rows: int = 0                    # valid rows streamed

    @property
    def serial_s(self) -> float:
        """What a load-then-execute loop would have cost (no overlap)."""
        return self.load_s + self.exec_s

    @property
    def overlap_saved_s(self) -> float:
        return max(0.0, self.serial_s - self.wall_s)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["serial_s"] = self.serial_s
        d["overlap_saved_s"] = self.overlap_saved_s
        return d


class _InjectedCrash(RuntimeError):
    """Raised by the ``crash_after`` test/ops hook — simulates preemption
    mid-extraction after N chunks committed to the journal."""


class ChunkedExecutor:
    """Drives one Study over a ``ChunkStore`` (see module docstring).

    ``checkpoint_dir`` enables the resumable journal; ``prefetch=False``
    degrades to serial load-then-execute (the bench baseline);
    ``crash_after=k`` kills the run after k chunks committed (tests)."""

    def __init__(self, store: ChunkStore, engine: str = "xla",
                 predicate_engine: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None, prefetch: bool = True,
                 allow_unsafe: bool = False,
                 crash_after: Optional[int] = None) -> None:
        self.store = store
        self.engine = engine
        self.predicate_engine = predicate_engine
        self.checkpoint_dir = checkpoint_dir
        self.prefetch = bool(prefetch)
        self.allow_unsafe = bool(allow_unsafe)
        self.crash_after = crash_after
        self.report = ChunkedReport()

    # -- planning ------------------------------------------------------------
    def _resident_env(self, study, tables) -> Dict[str, ColumnarTable]:
        env = self.store.resident_tables()
        env.update(study._sources)
        env.update(tables or {})
        return env

    def _chunk_env(self, resident: Dict[str, ColumnarTable],
                   chunk: ColumnarTable) -> Dict[str, ColumnarTable]:
        env = dict(resident)
        env[self.store.source] = chunk
        return env

    def _plan(self, study, resident: Dict[str, ColumnarTable]) -> Plan:
        raw = study.plan()
        needs_stats = any(n.op in ("expand_join", "slice_time")
                          and n.get("capacity") is None for n in raw.nodes)
        peng = self.predicate_engine or "auto"
        if not needs_stats:
            return study.optimized_plan(tables=None, n_shards=1,
                                        predicate_engine=peng,
                                        engine=self.engine)
        # content-dependent capacities: plan each chunk exactly, then take
        # the elementwise max so one executable serves every chunk
        plans = []
        for ci in range(self.store.n_chunks):
            env = self._chunk_env(resident, self.store.chunk_table(ci))
            plans.append(_optimizer.optimize(
                raw, tables=env, n_shards=1, predicate_engine=peng,
                engine=self.engine))
        return _merge_capacity_plans(plans)

    def _preflight(self, study, plan: Plan,
                   env0: Dict[str, ColumnarTable]) -> None:
        from repro.study.analyze import PlanValidationError, analyze, errors

        diags = analyze(plan, tables=env0, n_shards=1,
                        n_patients=study.n_patients,
                        chunk_capacity=self.store.chunk_capacity)
        if errors(diags):
            raise PlanValidationError(diags)
        unsafe = chunk_unsafe_ops(plan, self.store.source)
        if unsafe and not self.allow_unsafe:
            ops = ", ".join(f"#{i}:{op}" for i, op in unsafe)
            raise ValueError(
                f"plan has chunk-unsafe ops downstream of the chunked scan "
                f"({ops}): per-patient folds/dedupe see one chunk at a time, "
                "so chunked results would differ from the resident path when "
                "a patient's rows span chunks.  Run resident, or pass "
                "allow_unsafe=True to accept approximate semantics")

    # -- checkpoint journal --------------------------------------------------
    def _stamp(self, plan: Plan, n_patients: int) -> str:
        blob = repr((plan.key(), self.engine, self.predicate_engine,
                     int(n_patients),
                     self.store.fingerprint())).encode()
        return hashlib.sha256(blob).hexdigest()

    def _journal_path(self) -> str:
        return os.path.join(self.checkpoint_dir, JOURNAL_NAME)

    def _spill_dir(self, ci: int) -> str:
        return os.path.join(self.checkpoint_dir, "spill", f"chunk_{ci:05d}")

    def _read_journal(self, stamp: str) -> Set[int]:
        """Completed chunk ids from a valid journal; a stamp mismatch (other
        plan/store/engine) discards the journal rather than mixing state.

        Parsed line by line: a kill mid-append leaves a torn final line, and
        that must cost exactly the one uncommitted chunk — not every chunk
        before it.  Parsing stops at the first undecodable line; everything
        already read stays resumable (the append-only protocol guarantees
        all prior lines are complete).  The valid prefix length is kept in
        ``_journal_keep_bytes`` so ``_start_journal`` can truncate the torn
        tail before new lines append onto it."""
        path = self._journal_path()
        self._journal_keep_bytes = None
        if not os.path.exists(path):
            return set()
        lines = []
        keep = 0
        try:
            with open(path, "rb") as f:
                for raw in f:
                    if not raw.endswith(b"\n"):
                        break            # unterminated tail: treat as torn
                    ln = raw.decode("utf-8", errors="replace")
                    if not ln.strip():
                        keep += len(raw)
                        continue
                    try:
                        lines.append(json.loads(ln))
                    except json.JSONDecodeError:
                        break            # torn tail: keep the valid prefix
                    keep += len(raw)
            self._journal_keep_bytes = keep
        except OSError:
            return set()
        if not lines or lines[0].get("kind") != "header" \
                or lines[0].get("stamp") != stamp:
            return set()
        done: Set[int] = set()
        for ln in lines[1:]:
            if ln.get("kind") == "chunk":
                done.add(int(ln["index"]))
        return done

    def _start_journal(self, stamp: str, resumed: Set[int]) -> None:
        os.makedirs(self.checkpoint_dir, exist_ok=True)
        path = self._journal_path()
        if resumed:
            # keep appending to the valid journal — after cutting off any
            # torn tail, or the next append would concatenate onto it and
            # corrupt a good record
            keep = getattr(self, "_journal_keep_bytes", None)
            if keep is not None and keep < os.path.getsize(path):
                with open(path, "r+b") as f:
                    f.truncate(keep)
                    f.flush()
                    os.fsync(f.fileno())
            return
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "header", "stamp": stamp,
                                "n_chunks": self.store.n_chunks}) + "\n")
            f.flush()
            os.fsync(f.fileno())

    def _commit_chunk(self, ci: int, vals: Dict[int, Any],
                      counts: Dict[int, int],
                      stats: Dict[int, Dict[str, int]], plan: Plan) -> None:
        """Spill chunk ci's kept values, then append+fsync the journal line.
        The line is written only after the spill completes, so a kill at any
        point leaves either a resumable chunk or a re-executable one."""
        sd = self._spill_dir(ci)
        os.makedirs(sd, exist_ok=True)
        table_ids = []
        for nid, v in vals.items():
            if isinstance(v, ColumnarTable):
                save_columnar_arrays(
                    {k: np.asarray(c) for k, c in v.columns.items()},
                    np.asarray(v.valid), os.path.join(sd, f"table_{nid}"),
                    compressed=False)
                table_ids.append(nid)
        bits = {str(nid): np.asarray(v) for nid, v in vals.items()
                if not isinstance(v, ColumnarTable)}
        np.savez(os.path.join(sd, "bits"), **bits)
        meta = {"counts": {str(k): int(v) for k, v in counts.items()},
                "stats": {str(k): {kk: int(vv) for kk, vv in d.items()}
                          for k, d in stats.items()},
                "tables": table_ids}
        tmp = os.path.join(sd, "meta.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, os.path.join(sd, "meta.json"))
        # the rename itself must be durable before the journal line commits
        # the chunk, or a crash could journal a chunk whose meta.json the
        # directory never learned about
        _fsync_dir(sd)
        with open(self._journal_path(), "a") as f:
            f.write(json.dumps({"kind": "chunk", "index": ci}) + "\n")
            f.flush()
            os.fsync(f.fileno())

    def _load_spill(self, ci: int) -> Tuple[Dict[int, Any], Dict[int, int],
                                            Dict[int, Dict[str, int]]]:
        sd = self._spill_dir(ci)
        with open(os.path.join(sd, "meta.json")) as f:
            meta = json.load(f)
        vals: Dict[int, Any] = {}
        for nid in meta["tables"]:
            cols, valid = load_columnar_arrays(
                os.path.join(sd, f"table_{nid}"))
            vals[int(nid)] = ColumnarTable.from_columns(cols, valid=valid)
        with np.load(os.path.join(sd, "bits.npz")) as z:
            for k in z.files:
                vals[int(k)] = z[k]
        counts = {int(k): int(v) for k, v in meta["counts"].items()}
        stats = {int(k): dict(d) for k, d in meta["stats"].items()}
        return vals, counts, stats

    # -- the run -------------------------------------------------------------
    def run(self, study, tables: Optional[Dict[str, ColumnarTable]] = None,
            log: Optional[OperationLog] = None):
        """Execute ``study`` over the store; returns its ``StudyResult``
        (bit-identical valid rows / cohort words / features to
        ``Study.run`` over the unpartitioned star).  ``self.report`` holds
        the timing + resume audit afterwards."""
        store = self.store
        store.validate()
        resident = self._resident_env(study, tables)
        plan = self._plan(study, resident)
        dep = chunk_dependent_ids(plan, store.source)
        plan = _unwrap_compacted_concats(plan, dep)
        chunk0 = store.chunk_table(0)
        self._preflight(study, plan, self._chunk_env(resident, chunk0))

        keep = _executor.keep_ids(plan)
        cohort_keep = [i for i in keep
                       if plan.nodes[i].op in ("cohort_from_events",
                                               "cohort_op")]
        log = log if log is not None else OperationLog()
        rep = self.report = ChunkedReport(n_chunks=store.n_chunks)
        compiles0 = _executor.jit_cache_info()["compiles"]

        stamp = self._stamp(plan, study.n_patients)
        done: Set[int] = set()
        if self.checkpoint_dir is not None:
            done = self._read_journal(stamp)
            self._start_journal(stamp, done)

        # merge state
        dep_tables: Dict[int, Dict[int, ColumnarTable]] = {}  # nid -> ci -> t
        indep_vals: Dict[int, Any] = {}
        bits_acc: Dict[int, np.ndarray] = {}
        counts_dep: Dict[int, int] = {}
        counts_indep: Dict[int, int] = {}
        stats_dep: Dict[int, Dict[str, int]] = {}
        stats_indep: Dict[int, Dict[str, int]] = {}

        def merge(ci: int, vals: Dict[int, Any], counts: Dict[int, int],
                  stats: Dict[int, Dict[str, int]]) -> None:
            for nid, v in vals.items():
                if nid in cohort_keep or not isinstance(v, ColumnarTable):
                    w = np.asarray(v)
                    if nid in bits_acc:
                        bits_acc[nid] = bits_acc[nid] | w
                    else:
                        bits_acc[nid] = w
                elif nid in dep:
                    dep_tables.setdefault(nid, {})[ci] = v
                    rep.rows += int(counts.get(nid, 0))
                elif nid not in indep_vals:
                    indep_vals[nid] = v
            for nid, c in counts.items():
                if nid in dep:
                    counts_dep[nid] = counts_dep.get(nid, 0) + int(c)
                elif nid not in counts_indep:
                    counts_indep[nid] = int(c)
            for nid, d in stats.items():
                if nid in dep:
                    stats_dep[nid] = _sum_stats(stats_dep.get(nid, {}), d)
                elif nid not in stats_indep:
                    stats_indep[nid] = {k: int(v) for k, v in d.items()}

        for ci in sorted(done):
            vals, counts, stats = self._load_spill(ci)
            merge(ci, vals, counts, stats)
            rep.resumed += 1
            log.record(op=f"chunked:resume:{ci}", inputs={}, outputs={},
                       params={"chunk": ci, "rows":
                               store.manifest.chunks[ci].rows})

        todo = [ci for ci in range(store.n_chunks) if ci not in done]

        def _load(ci: int, loop: tracing.Span
                  ) -> Tuple[ColumnarTable, float]:
            with tracing.span("chunked.load", parent=loop, chunk=ci) as s:
                # chunk 0 was already loaded for planning/preflight — reuse it
                t = chunk0 if ci == 0 else store.chunk_table(ci)
                jax.block_until_ready(t.valid)  # staging done, not just enqueued
            return t, s.seconds

        pool = ThreadPoolExecutor(max_workers=1) if self.prefetch and todo \
            else None
        with tracing.span("chunked.loop") as loop:
            try:
                fut = pool.submit(_load, todo[0], loop) if pool else None
                for pos, ci in enumerate(todo):
                    if self.crash_after is not None and \
                            rep.executed >= self.crash_after:
                        raise _InjectedCrash(
                            f"injected crash after {rep.executed} chunks")
                    chunk, load_s = fut.result() if fut else _load(ci, loop)
                    rep.load_s += load_s
                    if pool and pos + 1 < len(todo):
                        fut = pool.submit(_load, todo[pos + 1], loop)
                    stats_sink: Dict[int, Dict[str, int]] = {}
                    with tracing.span("chunked.exec", chunk=ci) as ex:
                        vals = _executor.execute(
                            plan, self._chunk_env(resident, chunk),
                            n_patients=study.n_patients, engine=self.engine,
                            log=None, jit=True, stats_sink=stats_sink,
                            predicate_engine=self.predicate_engine)
                        jax.block_until_ready(vals)
                    exec_s = ex.seconds
                    rep.exec_s += exec_s
                    counts = {i: int(np.asarray(vals[i].count))
                              if isinstance(vals[i], ColumnarTable)
                              else int(np.bitwise_count(np.asarray(vals[i]))
                                       .sum())
                              for i in vals}
                    if self.checkpoint_dir is not None:
                        self._commit_chunk(ci, vals, counts, stats_sink, plan)
                    merge(ci, vals, counts, stats_sink)
                    rep.executed += 1
                    log.record(op=f"chunked:chunk:{ci}", inputs={},
                               outputs={},
                               params={"chunk": ci,
                                       "load_s": round(load_s, 6),
                                       "exec_s": round(exec_s, 6)})
            finally:
                if pool:
                    pool.shutdown(wait=False, cancel_futures=True)
        rep.wall_s = loop.seconds
        rep.compiles = _executor.jit_cache_info()["compiles"] - compiles0

        # -- merge into one StudyResult -------------------------------------
        merged_vals: Dict[int, Any] = dict(indep_vals)
        # branch-aware concat merge: chunk order would interleave the
        # branches the resident path lays out branch-major (module docstring)
        windows: Dict[int, List[Tuple[int, int, int]]] = {}
        concat_ids = [nid for nid in dep_tables
                      if plan.nodes[nid].op == "concat"
                      and len(plan.nodes[nid].inputs) > 1]
        if concat_ids:
            probe: Set[int] = set()
            for nid in concat_ids:
                probe.update(_concat_probe_ids(plan, nid, dep))
            rows_of = _padded_rows(
                plan, self._chunk_env(resident, chunk0), study.n_patients,
                self.engine, self.predicate_engine, sorted(probe))
            for nid in concat_ids:
                windows[nid] = _concat_windows(plan, nid, dep, rows_of)
        for nid, by_chunk in dep_tables.items():
            if nid in windows:
                cis = sorted(by_chunk)
                parts = []
                for k, a, b in windows[nid]:
                    # chunk-independent branches are identical every chunk —
                    # take the window once, not once per chunk
                    for ci in (cis if k in dep else cis[:1]):
                        parts.append(_slice_rows(by_chunk[ci], a, b))
                t = parts[0] if len(parts) == 1 else ColumnarTable.concat(parts)
                merged_vals[nid] = t
                # the per-chunk count sum double-counts chunk-independent
                # branches; the merged popcount is exact either way
                counts_dep[nid] = int(
                    np.bitwise_count(np.asarray(t.valid)).sum())
                continue
            parts = [by_chunk[ci] for ci in sorted(by_chunk)]
            merged_vals[nid] = (parts[0] if len(parts) == 1
                                else ColumnarTable.concat(parts))
        for nid, w in bits_acc.items():
            merged_vals[nid] = jnp.asarray(w)

        counts = dict(counts_indep)
        counts.update(counts_dep)
        counts.update(_replay_cohort_counts(
            plan, {i: bits_acc[i] for i in bits_acc
                   if plan.nodes[i].op == "cohort_from_events"}))
        # dependent table counts: the merged table's popcount, already the
        # per-chunk sum; nothing to fix up
        join_stats = dict(stats_indep)
        join_stats.update(stats_dep)
        _executor.record_plan(plan, counts, log, self.engine,
                              stats=join_stats,
                              predicate_engine=self.predicate_engine)
        for i, d in join_stats.items():
            d.setdefault("stage", plan.nodes[i].label())
        log.record(op="chunked:summary", inputs={}, outputs={},
                   params=rep.to_json())
        return study._finish_result(plan, merged_vals, join_stats, log)
