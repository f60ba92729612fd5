"""``peak_bytes_in_use`` of the fullest device used, read after the window
and before the reference runs, over 1e9."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
