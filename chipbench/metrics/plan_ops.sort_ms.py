"""Device time of sort ops per study: the summed duration of the device's
``sort`` instructions (``ColumnarTable.sort_by``'s passes in the flatten
joins, dedupes and transforms) inside the traced studies, over their
number, averaged over the chips."""
import numpy as np


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = t.whole_spans("bench.study")
    if not spans:
        return None
    total = np.mean([sum(t.family_ns(d, s, e, "sort") for s, e in spans)
                     for d in t.ops])
    return total / len(spans) * 1e-6
