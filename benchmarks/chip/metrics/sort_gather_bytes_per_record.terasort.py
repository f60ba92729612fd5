"""The shuffle engine's ``sort_gather_bytes`` counter, its delta over the
window, per input record: the bytes of the leaves that a sort stage gathers
after its sorts, because they cannot ride in them."""


def read(run):
    moved = run.counters.get("shuffle/sort_gather_bytes")
    if moved is None or not run.records:
        return None
    return moved / run.records
