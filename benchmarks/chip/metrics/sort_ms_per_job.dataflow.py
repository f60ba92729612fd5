"""Device time of the XLA sort ops (the shuffle engine's sort stage, its
argsorts and the local merge) per job, averaged over the cell's devices."""
from benchmarks.chip import xtrace


def is_sort(name: str) -> bool:
    return xtrace.op_family(name).startswith("sort")


def read(run):
    return xtrace.op_ms_per_job(run, is_sort)
