"""Input records of every job completed in the window, over the window."""


def read(run):
    return run.records / run.window_s if run.jobs else None
