"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
cell's devices, in %."""
from benchmarks.chip import xtrace


def read(run):
    return xtrace.idle_share(run)
