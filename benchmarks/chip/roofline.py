"""Peaks of each device kind and the least bytes each kernel must move.

The least bytes count every input leaf of the kernel read once and every
output written once, from the shapes of the call; no implementation can move
fewer, so a share of the roofline computed from them cannot pass 100% unless
the kernel time leaves out part of the work.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def segment_reduce_bytes(rows: int, cols: int, itemsize: int) -> int:
    """``segment_reduce_fwd`` over (rows, cols) values: reads the values and
    one int32 boundary flag per row, writes the (rows, cols) scan."""
    return rows * cols * itemsize * 2 + rows * 4


def least_seconds(nbytes: float, flops: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "flops")
