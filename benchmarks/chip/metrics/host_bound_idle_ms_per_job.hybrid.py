"""Device idle time per job that the host's own work explains: the idle
gaps of the window (no op on the device) that fall inside a program span
of host work, ``collect:``, ``compile:``, ``stage:``/``wide:`` (host
dispatch), ``import:`` or ``native:``, on any host thread; mean over the
cell's devices, in ms (``progspans``). The run's log also names the longest
idle gaps by the program span open in them."""
from benchmarks.chip import progspans


def read(run):
    value = progspans.host_bound_idle_ms_per_job(run)
    if value is not None:
        progspans.log_gap_labels(run)
    return value
