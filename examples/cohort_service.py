"""Multi-tenant cohort-query service over one resident star schema.

Several analyst teams (tenants) issue cohort studies against the SAME claims
database.  The ``CohortQueryService`` keeps the star schema resident on
device and serves every tenant through three shared layers:

  * admission — slot-based window with per-tenant in-flight quotas and
    priority queueing (``serving.batching.SlotScheduler``);
  * plan normalization — each study's literals (thresholds, code lists) are
    hoisted out of the plan, so all tenants' structurally-equal studies
    share ONE compiled executable;
  * cross-tenant subgraph cache — shared plan prefixes (the flatten joins,
    the common code-whitelist masks) are computed once and served from a
    content-addressed device cache for every later query.

Run:  PYTHONPATH=src python examples/cohort_service.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import DCIR_SCHEMA, drug_dispenses, medical_acts_dcir
from repro.data.io import save_star
from repro.data.synthetic import SyntheticConfig, generate_dcir
from repro.compile_cache import enable_compile_cache
from repro.study import CohortQueryService, ServiceConfig, Study, col

enable_compile_cache()

cfg = SyntheticConfig(n_patients=2_000, seed=7)
P = cfg.n_patients

# the hospital's shared clinical vocabulary: every team filters drugs to the
# same whitelist (a shared, cacheable plan prefix) ...
WHITELIST = list(range(0, 400, 3))


def team_study(threshold: int) -> Study:
    """One team's study: same shape for every team, team-specific follow-up
    threshold — a literal the service hoists out of the compiled program."""
    s = Study(n_patients=P)
    s.flatten(DCIR_SCHEMA)
    s.extract(drug_dispenses(codes=WHITELIST), name="drugs")
    s.extract(medical_acts_dcir(), name="acts")
    s.filter("acts", col("value") >= threshold, name="acts_hi")
    s.cohort("exposed", "drugs")
    s.cohort("final", "exposed & acts_hi")
    return s


# -- resident star schema: persist once, load once per table version ---------
with tempfile.TemporaryDirectory() as d:
    save_star(generate_dcir(cfg), d)
    svc = CohortQueryService.from_npz_dir(
        d, config=ServiceConfig(n_slots=4, per_tenant_inflight=2,
                                cache_budget_bytes=128 << 20))

# -- four tenants, eight queries each, tenant-specific thresholds -------------
tickets = []
for q in range(8):
    for i, tenant in enumerate(["cardio", "onco", "pharma", "public-health"]):
        t = svc.submit(team_study(threshold=40 + 20 * i + q),
                       tenant=tenant, priority=1 if tenant == "cardio" else 0)
        tickets.append(t)

svc.drain()

done = [t for t in tickets if t.status == "done"]
print(f"completed {len(done)}/{len(tickets)} queries")
for t in done[:4]:
    final = t.result.cohorts["final"]
    print(f"  {t.tenant:14s} final cohort: {final.subject_count():5d} subjects  "
          f"(cache {t.cache_hits} hits / {t.cache_misses} misses, "
          f"{t.latency_s * 1e3:.1f} ms)")

s = svc.stats
print(f"\nexecutables compiled : {s.compile_count} (for {s.queries} queries)")
print(f"subgraph cache       : {s.cache_hits} hits / {s.cache_misses} misses "
      f"({100 * s.hit_rate():.0f}% hit rate), "
      f"{s.cache_bytes / 1e6:.1f} MB resident")
print(f"audit log            : {len(svc.log.entries)} entries "
      f"(see OperationLog.to_json())")
