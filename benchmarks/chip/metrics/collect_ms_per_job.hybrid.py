"""Host time per job that actions spend turning fetched results into Python
objects: the program's ``collect:<action>`` spans (``core/dataframe.py``:
rows out of host blocks, the key/count dict of ``count_by_value``), summed
over threads. The fetch itself (``fetch:<action>``) is a wait for the device
and is left out."""


def read(run):
    spans = [s for s in run.tracer_spans if s.name.startswith("collect:")]
    if not spans or not run.jobs:
        return None
    return sum(s.dur for s in spans) * 1e3 / run.jobs
