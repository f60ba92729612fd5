"""Misses of the program's jit caches per job, from the window's counter
deltas: fused-stage plans (``stages/plan_cache_misses``), jitted row maps
(``stages/vmap_misses``), wide-stage plans (``shuffle/wide_plan_misses``)
and, where the job's counters carry them, collective plans
(``coll/coll_plan_misses``). Every miss builds an executable, or loads one
from the compilation cache, inside the window. A program without the
``vmap_misses`` counter gives no reading."""

KEYS = ("stages/plan_cache_misses", "stages/vmap_misses",
        "shuffle/wide_plan_misses", "coll/coll_plan_misses")


def read(run):
    if "stages/vmap_misses" not in run.counters or not run.jobs:
        return None
    return sum(run.counters.get(k, 0) for k in KEYS) / run.jobs
