"""Planning time per study: the wall of the program's ``study.optimize``
span (``Study.optimized_plan``: the optimizer's rewrites and
``plan_capacities``' host-side join simulation, which reads the join keys
of the star to the host) inside each traced study, averaged over the
studies.  The device has nothing to run yet, so all of it is device idle."""
from chipbench import spans


def read(run):
    return spans.per_study(run, lambda st: spans.wall_ms(st,
                                                         "study.optimize"))
