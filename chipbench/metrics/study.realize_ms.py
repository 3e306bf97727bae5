"""Realization time per study: the wall of the program's ``study.realize``
span (``Study._finish_result``: cohort algebra, the flowchart's counts and
the two feature exports, run op by op from the host) inside each traced
study, averaged over the studies."""
from chipbench import spans


def read(run):
    return spans.per_study(run, lambda st: spans.wall_ms(st, "study.realize"))
