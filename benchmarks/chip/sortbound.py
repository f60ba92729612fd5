"""The least bytes a sort of whole records must move: every record read once
and written once. The count comes from the record count and width that the
configuration states, never from the program, so a share of the roofline
computed from it reads the same work whatever implements the sort, and no
implementation can pass 100%."""


def least_sort_bytes(records: int, record_bytes: int) -> int:
    return 2 * records * record_bytes
