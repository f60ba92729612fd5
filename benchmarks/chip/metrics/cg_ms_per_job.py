"""Device time of the native CG program (``apps/stencil.py`` ``cg_native``,
the executable ``jit_prog``) per job, averaged over the cell's devices."""
from benchmarks.chip import xtrace


def is_cg(name: str) -> bool:
    return name.startswith("jit_prog(")


def read(run):
    return xtrace.op_ms_per_job(run, is_cg, line="modules")
