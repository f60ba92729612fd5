"""The scheduler's ``lock_wait`` spans (``JobTracer``, from the timestamps
the job scheduler stamps on each task), summed over the window, per job."""


def read(run):
    tasks = [s for s in run.tracer_spans if s.cat == "task"]
    if not tasks or not run.jobs:
        return None
    waits = sum(s.dur for s in run.tracer_spans if s.name == "lock_wait")
    return waits * 1e3 / run.jobs
