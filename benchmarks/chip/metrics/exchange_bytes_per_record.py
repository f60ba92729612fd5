"""The shuffle engine's ``bytes_moved`` counter, its delta over the window,
per input record: the bytes the exchanges ship, capacity padding included."""


def read(run):
    moved = run.counters.get("shuffle/bytes_moved")
    if not moved or not run.records:
        return None
    return moved / run.records
