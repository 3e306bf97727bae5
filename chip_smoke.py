#!/usr/bin/env python3
"""Smoke run of SCALPEL3's three main paths on a TPU chip.

Run from the repository root::

    python chip_smoke.py                  # one chip: batch study, service, chunked
    python chip_smoke.py --chips 4        # four chips: the patient-sharded paths only

One process, no child processes.  The script refuses to run without a TPU
(it never falls back to the CPU) and checks every result by the repo's own
means: the Pallas-engine study must be bit-identical to the XLA/jnp study and
agree with plain numpy recounts over the generator's host arrays, every
service ticket must come back ``done`` and bit-identical to a solo run, and a
chunked stream must merge to the resident result with one compile.  Any
failed check exits non-zero before the last line, which on success is
exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": 1}}

Earlier lines report the data size, compile seconds (set-up) and per-phase
wall times; those walls are a smoke run's, not metrics.  Scratch files go
under ``--out-dir`` and are removed before exit.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    DCIR_SCHEMA, PMSI_MCO_SCHEMA, diagnoses, drug_dispenses, flatten_star,
    hospital_stays, medical_acts_dcir, medical_acts_pmsi,
)
from repro.core.bitset import unpack_np  # noqa: E402
from repro.core.columnar import NULL_INT  # noqa: E402
from repro.data.synthetic import SyntheticConfig, generate_snds  # noqa: E402
from repro.study import (  # noqa: E402
    CohortQueryService, ServiceConfig, Study, clear_jit_cache, col,
    compile_spec, normalize,
)
from repro.study.plan import PREDICATE_OPS  # noqa: E402

STUDY_START = 14_600
STUDY_END = STUDY_START + 3 * 365
DEFAULT_PATIENTS = 262_144
SPEC_PATH = os.path.join(ROOT, "tests", "goldens", "cohort_study_spec.json")


class SmokeFailure(AssertionError):
    """A smoke check failed; the script exits non-zero."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def device_arrays(out):
    """The arrays a phase produced, for ``block_until_ready``: a
    ``StudyResult`` is not a pytree, so its tables, cohorts and features
    are listed."""
    if hasattr(out, "events") and hasattr(out, "cohorts"):
        return (out.events, [c.subjects for c in out.cohorts.values()],
                out.features)
    return out


# ---------------------------------------------------------------------------
# timing: wall per phase after block_until_ready, compile seconds per program
# ---------------------------------------------------------------------------
class Clock:
    """Per-phase wall time and the backend compile seconds inside it (read
    from JAX's own compile-duration events), printed as a smoke run's
    numbers, not as metrics."""

    def __init__(self) -> None:
        self.compiles = []              # (phase, fun_name, seconds)
        self.walls = defaultdict(float)
        self.phase = "setup"
        self.t0 = time.perf_counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((self.phase, kw.get("fun_name", "?"),
                                  float(duration)))

    def run(self, phase: str, fn, *args, **kwargs):
        self.phase = phase
        t0 = time.perf_counter()
        say(f"[{t0 - self.t0:.0f} s] {phase}")
        out = fn(*args, **kwargs)
        jax.block_until_ready(jax.tree.leaves(device_arrays(out)))
        self.walls[phase] += time.perf_counter() - t0
        self.phase = "setup"
        return out

    def report(self) -> None:
        per_phase = defaultdict(float)
        per_prog = defaultdict(float)
        for phase, name, s in self.compiles:
            per_phase[phase] += s
            per_prog[(phase, name)] += s
        for (phase, name), s in sorted(per_prog.items(),
                                       key=lambda kv: -kv[1])[:12]:
            say(f"compile (set-up) {phase}/{name}: {s:.3f} s")
        for phase, wall in self.walls.items():
            say(f"smoke wall (not a metric) {phase}: {wall:.3f} s, "
                f"of which compile {per_phase[phase]:.3f} s")


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def make_star(n_patients: int, seed: int):
    """The SNDS-shaped star (DCIR + PMSI-MCO) from ``seed``, plus host numpy
    copies of every table (valid rows only) for the numpy recounts."""
    dcir, pmsi = generate_snds(SyntheticConfig(n_patients=n_patients,
                                               seed=seed))
    star = {**dcir, **pmsi}
    host = {}
    for name, t in star.items():
        keep = unpack_np(np.asarray(t.valid), t.capacity)
        host[name] = {c: np.asarray(v)[keep] for c, v in t.columns.items()}
    return star, host


# ---------------------------------------------------------------------------
# studies
# ---------------------------------------------------------------------------
def cohort_study(n_patients: int) -> Study:
    """``examples/cohort_study.py`` over the raw star: the joins are plan
    nodes, so flattening runs in the same program as tasks (a)-(g)."""
    return (Study(n_patients=n_patients, window=(STUDY_START, STUDY_END))
            .flatten(DCIR_SCHEMA)
            .flatten(PMSI_MCO_SCHEMA)
            .patients("IR_BEN")
            .extract(drug_dispenses(), name="drug_purchases")
            .extract(drug_dispenses()
                     .filtered(col("cip13").isin(range(65))
                               & col("execution_date").between(STUDY_START,
                                                               STUDY_END)),
                     name="prevalent_drugs")
            .extract(medical_acts_dcir(), name="acts")
            .extract(medical_acts_pmsi(), name="hospital_acts")
            .extract(diagnoses(), name="diagnoses")
            .extract(hospital_stays(), name="stays")
            .cohort("base", "extract_patients")
            .transform("exposures", "drug_purchases", name="exposures",
                       purview_days=60)
            .concat("all_acts", "acts", "hospital_acts")
            .transform("fractures", "all_acts", "diagnoses", name="fractures",
                       fracture_act_codes=list(range(30)),
                       fracture_diag_codes=list(range(40)))
            .transform("follow_up", "extract_patients", "drug_purchases",
                       name="follow_up", study_end=STUDY_END)
            .cohort("exposed", "exposures")
            .cohort("fractured", "fractures")
            .cohort("final", "(exposed & base) - fractured")
            .flow("base", "exposed", "final")
            .featurize("X", cohort="final", kind="dense",
                       n_buckets=36, bucket_days=31, n_features=128)
            .featurize("tokens", cohort="final", kind="tokens", seq_len=256))


def quickstart_study(n_patients: int, codes=range(30)) -> Study:
    """``examples/quickstart.py``: flatten, two extractors, cohort algebra
    and a flow — chunk-safe (no transform, no dedupe)."""
    return (Study(n_patients=n_patients)
            .flatten(DCIR_SCHEMA)
            .extract(drug_dispenses(), name="drug_purchases")
            .extract(medical_acts_dcir(codes=list(codes)), name="acts")
            .patients("IR_BEN")
            .cohort("base", "extract_patients")
            .cohort("drugged", "drug_purchases")
            .cohort("final", "drugged & base - acts")
            .flow("base", "drugged", "final"))


def drug_query(n_patients: int, codes, date_from: int) -> Study:
    """A tenant's query: a code whitelist and a date threshold, the two
    literal kinds the service hoists into kernel operands."""
    return (Study(n_patients=n_patients)
            .flatten(DCIR_SCHEMA)
            .extract(drug_dispenses(codes=list(codes))
                     .filtered(col("execution_date") >= date_from),
                     name="drugs")
            .patients("IR_BEN")
            .cohort("base", "extract_patients")
            .cohort("drugged", "drugs")
            .cohort("final", "drugged & base"))


# ---------------------------------------------------------------------------
# result comparison
# ---------------------------------------------------------------------------
def fingerprint(res, sharded: bool = False) -> dict:
    """A StudyResult as host numpy arrays: valid event rows in order, cohort
    words, flow counts, feature leaves and join statistics.  Taking it
    frees nothing on the device by itself; callers drop the result.

    ``sharded=True`` is the form a patient-sharded run is compared in: a
    mesh run exchanges rows by patient, so its event rows are put in one
    canonical order, and its per-join statistics (which include the
    exchange nodes a single device prunes) are left out."""
    fp = {}
    for k, t in res.events.items():
        fp[f"events/{k}/count"] = np.asarray(int(t.count))
        rows = t.to_numpy()
        if sharded and rows:
            order = np.lexsort([rows[c] for c in sorted(rows)])
            rows = {c: v[order] for c, v in rows.items()}
        for c, v in rows.items():
            fp[f"events/{k}/{c}"] = v
    for k, c in res.cohorts.items():
        fp[f"cohorts/{k}"] = np.asarray(c.subjects)
    if res.flow is not None:
        fp["flow"] = np.asarray([c.subject_count() for c in res.flow.steps])
    for i, leaf in enumerate(jax.tree.leaves(res.features)):
        fp[f"features/{i}"] = np.asarray(leaf)
    if sharded:
        return fp
    fp["flatten_stats"] = np.asarray(json.dumps(
        {str(i): {k: int(v) for k, v in sorted(d.items()) if k != "stage"}
         for i, d in sorted(res.flatten_stats.items())}))
    return fp


def assert_identical(a: dict, b: dict, what: str) -> None:
    check(set(a) == set(b), f"{what}: different outputs "
          f"{sorted(set(a) ^ set(b))[:6]}")
    for k in a:
        check(a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
              and np.array_equal(a[k], b[k], equal_nan=a[k].dtype.kind == "f"),
              f"{what}: {k} differs")


def engines_logged(res) -> dict:
    """Engine recorded in the OperationLog for every predicate and compact
    node, keyed by log op name."""
    out = {}
    for e in res.log.entries:
        parts = e["op"].split(":")
        if parts[0] == "plan" and (parts[1] in PREDICATE_OPS
                                   or parts[1] == "compact"):
            out[e["op"]] = e["params"].get("engine")
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def batch_phase(clock: Clock, star, n_patients: int):
    """The cohort study twice — Pallas kernels (predicate, compaction, cohort
    algebra) and XLA/jnp — bit-identical, no join loss, every mask and
    compaction logged as pallas.  Returns the Pallas run's fingerprint."""
    study = cohort_study(n_patients)
    res = clock.run("batch_pallas", study.run, dict(star), engine="pallas",
                    predicate_engine="pallas")
    res.assert_no_loss()
    engines = engines_logged(res)
    check(bool(engines), "pallas run logged no predicate/compact node")
    not_pallas = {k: v for k, v in engines.items() if v != "pallas"}
    check(not not_pallas, f"pallas run fell back to another engine on "
          f"{sorted(not_pallas.items())[:4]}")
    fp_pallas = fingerprint(res)
    del res
    res = clock.run("batch_xla", study.run, dict(star), engine="xla",
                    predicate_engine="jnp")
    res.assert_no_loss()
    fp_xla = fingerprint(res)
    del res
    assert_identical(fp_pallas, fp_xla, "pallas vs xla/jnp study")
    say(f"batch study: {len(engines)} predicate/compact nodes ran on pallas; "
        f"pallas == xla/jnp on {len(fp_pallas)} arrays")
    return fp_pallas


def numpy_recount(host, n_patients: int) -> dict:
    """Rows of each extract output and the base cohort's size, recounted
    with plain numpy over the generator's host arrays (flattening = left
    joins of ER_PRS with its one-row-per-flow details, and of MCO_B with its
    one-to-many children)."""
    prs, pha, cam = host["ER_PRS"], host["ER_PHA"], host["ER_CAM"]
    b, d, a, ben = host["MCO_B"], host["MCO_D"], host["MCO_A"], host["IR_BEN"]
    flows = prs["flow_id"]

    def lookup(dim, value):
        """value of the first ``dim`` row per flow (NULL when none)."""
        order = np.argsort(dim["flow_id"], kind="stable")
        ids, vals = dim["flow_id"][order], dim[value][order]
        pos = np.searchsorted(ids, flows).clip(max=ids.size - 1)
        return np.where(ids[pos] == flows, vals[pos], NULL_INT)

    def distinct(t, keep, cols):
        return int(np.unique(np.stack([t[c][keep] for c in cols], axis=1),
                             axis=0).shape[0])

    cip = lookup(pha, "cip13")
    drug = cip != NULL_INT
    date = prs["execution_date"]
    prevalent = (drug & (cip >= 0) & (cip < 65)
                 & (date >= STUDY_START) & (date < STUDY_END))
    acts_in = np.isin(a["stay_id"], b["stay_id"]) & (a["ccam_code"] != NULL_INT)
    diag_in = np.isin(d["stay_id"], b["stay_id"]) & (d["icd_code"] != NULL_INT)
    pids = np.unique(ben["patient_id"])
    return {
        "drug_purchases": int(drug.sum()),
        "prevalent_drugs": int(prevalent.sum()),
        "acts": int((lookup(cam, "ccam_code") != NULL_INT).sum()),
        "hospital_acts": distinct(a, acts_in,
                                  ("stay_id", "ccam_code", "act_date")),
        "diagnoses": distinct(d, diag_in,
                              ("stay_id", "icd_code", "diag_kind")),
        "stays": int(np.unique(b["stay_id"]).size),
        "extract_patients": int(pids.size),
        "base": int(((pids >= 0) & (pids < n_patients)).sum()),
    }


def check_recount(fp: dict, counts: dict) -> None:
    got = {k: int(fp[f"events/{k}/count"]) for k in counts if k != "base"}
    base_words = fp["cohorts/base"]
    got["base"] = int(sum(bin(int(w)).count("1") for w in base_words))
    check(got == counts, f"numpy recount differs: study {got} vs numpy "
          f"{counts}")
    say(f"numpy recount agrees: {counts}")


def service_phase(clock: Clock, star, n_patients: int, spec: dict) -> dict:
    """Two tenants, eight queries (Python-built studies with different
    literals plus the golden wire spec) against one resident star: every
    ticket done, each bit-identical to a solo ``Study.run``, compiles no
    more than distinct plan shapes, no pallas->jnp demotion.  The wire spec
    reads the flat DCIR and PMSI_MCO tables, which are flattened once on
    the device and kept resident beside the raw star."""
    env = dict(star)
    for schema in (DCIR_SCHEMA, PMSI_MCO_SCHEMA):
        flat = jax.jit(lambda t, schema=schema: flatten_star(schema, t)[0])
        env[schema.name] = clock.run("service_flatten", flat,
                                     {t.name: star[t.name]
                                      for t in schema.all_tables()})
    # the literal variants share one compiled shape; every distinct study
    # also compiles once for its solo reference run
    q1 = drug_query(n_patients, range(0, 64), STUDY_START)
    q2 = drug_query(n_patients, range(64, 128), STUDY_START + 365)
    wire = compile_spec(spec)
    svc = CohortQueryService(env, config=ServiceConfig())
    tickets = []
    for tenant, studies in (("epi-a", (q1, q2, q1)), ("epi-b", (q2, q1, q2))):
        for s in studies:
            tickets.append((s, svc.submit(s, tenant=tenant)))
        tickets.append((wire, svc.submit_spec(spec, tenant=tenant)))
    clock.run("service_drain", svc.drain)
    st = svc.stats
    bad = [(t.tenant, t.status, repr(t.error)[:200]) for _, t in tickets
           if t.status != "done"]
    check(not bad, f"service tickets not done: {bad}")
    check(st.demotions == 0, f"service demoted {st.demotions} predicates")
    shapes = {normalize(s.optimized_plan(
        tables=svc._env, predicate_engine=svc.config.predicate_engine
        or "auto", engine=svc.config.engine)).plan.key()
        for s, _ in tickets}
    check(st.compile_count <= len(shapes),
          f"{st.compile_count} compiles for {len(shapes)} plan shapes")
    solo = {}
    for s, t in tickets:
        if id(s) not in solo:
            solo[id(s)] = fingerprint(clock.run("service_solo", s.run,
                                                dict(env)))
        assert_identical(fingerprint(t.result), solo[id(s)],
                         f"service ticket {t.tenant}#{t.seq} vs solo run")
        t.result = None
    say(f"service: {len(tickets)} tickets from 2 tenants done, "
        f"{st.compile_count} compiles for {len(shapes)} shapes, "
        f"cache hit rate {st.hit_rate():.3f}, demotions {st.demotions}")
    return st.snapshot()


def chunked_phase(clock: Clock, star, n_patients: int, out_dir: str,
                  n_chunks: int = 8) -> dict:
    """``partition_star`` into ``n_chunks`` and ``run_chunked`` the
    quickstart study: equal to the resident run, one compile."""
    from repro.data import partition_star

    study = quickstart_study(n_patients)
    resident = fingerprint(clock.run("chunked_resident", study.run,
                                     dict(star)))
    store_dir = os.path.join(out_dir, "chunk_store")
    shutil.rmtree(store_dir, ignore_errors=True)
    try:
        cap = star["ER_PRS"].capacity
        chunk = -(-cap // (n_chunks * 32)) * 32
        store = partition_star(dict(star), store_dir, source="ER_PRS",
                               chunk_capacity=chunk)
        check(store.n_chunks >= 4, f"only {store.n_chunks} chunks")
        clear_jit_cache()
        report = {}
        res = clock.run("chunked_stream", study.run_chunked, store,
                        report_sink=report)
        check(report.get("compiles") == 1,
              f"chunked run compiled {report.get('compiles')} times")
        assert_identical(fingerprint(res), resident,
                         "chunked vs resident run")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    say(f"chunked: {store.n_chunks} chunks, 1 compile, equal to resident; "
        f"rows streamed {report.get('rows')}")
    return report


def sharded_phase(clock: Clock, star, n_patients: int,
                  n_devices: int) -> None:
    """The patient-sharded paths on an ``n_devices`` mesh — flatten,
    extract and cohort plan via ``Study.run(mesh=)``, and four service
    queries via ``CohortQueryService(mesh=)`` — each bit-identical to the
    same work on one device."""
    from jax.sharding import Mesh

    devs = jax.devices()
    check(len(devs) >= n_devices, f"{n_devices} devices asked, "
          f"{len(devs)} present")
    mesh = Mesh(np.asarray(devs[:n_devices]), ("data",))
    study = quickstart_study(n_patients)
    single = clock.run("sharded_single", study.run, dict(star))
    sharded = clock.run("sharded_mesh", study.run, dict(star), mesh=mesh)
    single.assert_no_loss()
    sharded.assert_no_loss()
    assert_identical(fingerprint(single, sharded=True),
                     fingerprint(sharded, sharded=True),
                     f"{n_devices}-device vs 1-device study")
    del single, sharded
    queries = [drug_query(n_patients, range(0, 64), STUDY_START),
               drug_query(n_patients, range(64, 128), STUDY_START + 365),
               drug_query(n_patients, range(128, 192), STUDY_START),
               drug_query(n_patients, range(192, 256), STUDY_START + 730)]
    outs = {}
    for label, m in (("service_single", None), ("service_mesh", mesh)):
        svc = CohortQueryService(dict(star), mesh=m)
        ts = [svc.submit(q, tenant=f"t{i % 2}") for i, q in
              enumerate(queries)]
        clock.run(label, svc.drain)
        bad = [(t.status, repr(t.error)[:200]) for t in ts
               if t.status != "done"]
        check(not bad, f"{label}: tickets not done: {bad}")
        outs[label] = [fingerprint(t.result, sharded=True) for t in ts]
    for i, (x, y) in enumerate(zip(outs["service_single"],
                                   outs["service_mesh"])):
        assert_identical(x, y, f"{n_devices}-device vs 1-device query {i}")
    say(f"sharded: study and {len(queries)} service queries on "
        f"{n_devices} devices bit-identical to one device")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the patient-sharded paths on a "
                         "4-chip mesh, against one chip")
    ap.add_argument("--n-patients", type=int, default=DEFAULT_PATIENTS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=os.path.join(ROOT, "chiprun_out",
                                                      "chip_smoke"))
    args = ap.parse_args(argv)

    devs = jax.devices()
    d0 = devs[0]
    say(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        print("chip_smoke: no TPU found (JAX sees "
              f"{d0.platform}); refusing to run on another backend",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} asks for more than the "
              f"{len(devs)} TPU devices present", file=sys.stderr)
        return 2
    say(f"compile cache: {enable_compile_cache()}")
    os.makedirs(args.out_dir, exist_ok=True)
    clock = Clock()
    star, host = clock.run("data", make_star, args.n_patients, args.seed)
    say(f"data: n_patients={args.n_patients} seed={args.seed} rows="
        + ", ".join(f"{k}={int(t.count)}" for k, t in sorted(star.items())))
    with open(SPEC_PATH) as f:
        spec = json.load(f)
    try:
        if args.chips == 4:
            sharded_phase(clock, star, args.n_patients, 4)
        else:
            fp = batch_phase(clock, star, args.n_patients)
            check_recount(fp, numpy_recount(host, args.n_patients))
            del fp
            service_phase(clock, star, args.n_patients, spec)
            chunked_phase(clock, star, args.n_patients, args.out_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    clock.report()
    stats = d0.memory_stats() or {}
    say(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    # the devices the phases used: the first ``--chips`` of those present
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
