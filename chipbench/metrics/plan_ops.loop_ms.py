"""Device time of loops per study: the union of the device's ``while``
instructions inside the traced studies, over their number, averaged over
the chips.  The loops are the transforms' sequential ``lax.scan``s (the
fractures transform steps once per row of its acts) and
``columnar.cumsum``'s segment scans; the ops of a loop's body run inside
its ``while`` and are counted with it."""
import numpy as np


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = t.whole_spans("bench.study")
    if not spans:
        return None
    total = np.mean([sum(t.busy_ns(d, s, e, family="while")
                         for s, e in spans) for d in t.ops])
    return total / len(spans) * 1e-6
