"""Roofline share of the Pallas kernels (predicate, segment compaction,
cohort algebra) in the traced study window: the least time their calls
could take at the chip's peaks, from the byte models under
``chipbench/kernels/``, over their device time."""
from chipbench.trace import kernel_roofline


def read(run):
    if run.trace is None:
        return None
    return kernel_roofline(run.trace, run.kernels, run.peaks)
