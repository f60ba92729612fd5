"""Device time of the all-to-all ops (the shuffle engine's exchange) per
job, averaged over the cell's devices. HLO text names the op family
``all-to-all``; a v5e trace names it ``all_to_all``: both count."""
from benchmarks.chip import xtrace


def is_all_to_all(name: str) -> bool:
    return xtrace.op_family(name).replace("-", "_").startswith("all_to_all")


def read(run):
    return xtrace.op_ms_per_job(run, is_all_to_all)
