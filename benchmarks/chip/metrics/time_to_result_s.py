"""The window over the jobs completed in it: seconds from a job's
submission to its answer, in a closed loop of one job at a time."""


def read(run):
    return run.window_s / run.jobs if run.jobs else None
