"""Blocking reads of device values to the host per study: the
``host_syncs`` counters of every program span under each traced study's
``study.run`` root (the join-key reads of ``plan_capacities``, the wait for
the plan program, one read per join-statistics scalar, the flowchart's and
the feature exports' counts), averaged over the studies.  A count, not a
speed."""
from chipbench import spans


def read(run):
    return spans.per_study(run, lambda st: spans.counted(st, "host_syncs"))
