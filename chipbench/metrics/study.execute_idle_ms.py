"""Device idle inside execution per study: the program's ``study.execute``
span (``executor.execute``: dispatch of the study's one compiled program,
the wait for it, the per-scalar reads of the join statistics and the
provenance log) placed on the trace, less the union of the device's ops
inside it, averaged over the studies."""
from chipbench import spans


def read(run):
    return spans.per_study(run, lambda st: spans.idle_ms(run.trace, st,
                                                         "study.execute"))
