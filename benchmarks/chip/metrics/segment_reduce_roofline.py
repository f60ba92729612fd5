"""Share of its roofline that the ``segment_reduce`` Pallas kernel reaches:
the least time the chip could take for the kernel's calls in the window
(``roofline.segment_reduce_bytes`` of each call's shapes over the peak HBM
bandwidth of the device kind) over the device time of those calls, in %.

The kernel's ``pallas_call`` has no name of its own yet. In the trace it is
a ``tpu_custom_call`` whose output is the lane-dense (columns, rows/128,
128) scan; the ``prefix_scan`` kernel beside it writes a 2-D (rows/128, 128)
array.
"""
import re

from benchmarks.chip import roofline

_OUT_3D = re.compile(r"^%\S+ = [a-z]+\d+\[\d+,\d+,128\]\S* custom-call\(")


def is_segment_reduce(name: str) -> bool:
    return "tpu_custom_call" in name and bool(_OUT_3D.match(name))


def read(run):
    shape = run.kernel_shapes.get("segment_reduce")
    lo, hi = run.window_ns
    if not shape or run.trace is None or hi <= lo:
        return None
    calls = [s for i in range(run.devices) for s in run.trace.ops.get(i, [])
             if lo <= s.start and s.end <= hi and is_segment_reduce(s.name)]
    if not calls:
        return None
    nbytes = roofline.segment_reduce_bytes(shape["rows"], shape["cols"], shape["itemsize"])
    least, _ = roofline.least_seconds(nbytes * len(calls), 0.0,
                                      roofline.peaks(run.device_kind))
    return 100.0 * least / (sum(s.dur for s in calls) / 1e9)
