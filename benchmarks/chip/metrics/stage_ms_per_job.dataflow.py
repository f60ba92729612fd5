"""Device time of the shuffle engine's wide-stage executables (the jitted
``run`` of ``core/shuffle_plan.py``: sort stage, exchange, local merge and
post hook in one program) per job, averaged over the cell's devices."""
from benchmarks.chip import xtrace


def is_wide_stage(name: str) -> bool:
    return name.startswith("jit_run(")


def read(run):
    return xtrace.op_ms_per_job(run, is_wide_stage, line="modules")
