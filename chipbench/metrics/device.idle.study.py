"""Share of the traced study window in which no op ran on the device
(left out when the profiler's buffer filled: the ops it dropped would read
as idle)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.full:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
