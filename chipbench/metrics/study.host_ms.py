"""Host time per study: the wall of each traced study (the ``bench.study``
span, from ``Study.run`` to cohorts, flowchart and features on the host)
less the time in which an op ran on the device inside it, averaged over
the studies of the window.  It is the time planning (``study/optimizer.py``,
``plan_capacities``) and realization (``Study._finish_result``) hold the
device idle."""


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = t.whole_spans("bench.study")
    if not spans:
        return None
    dev = sorted(t.ops)[0]
    host = [(e - s) - t.busy_ns(dev, s, e) for s, e in spans]
    return sum(host) / len(host) * 1e-6
