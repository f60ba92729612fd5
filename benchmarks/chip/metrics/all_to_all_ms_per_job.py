"""Device time of the all-to-all ops (the shuffle engine's exchange) per
job, averaged over the cell's devices."""
from benchmarks.chip import xtrace


def is_all_to_all(name: str) -> bool:
    return xtrace.op_family(name).startswith("all-to-all")


def read(run):
    return xtrace.op_ms_per_job(run, is_all_to_all)
