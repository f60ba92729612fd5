"""Share of its roofline that the sort stage reaches on whole records: the
least time the chip could take to read and write each record of a job once
(``sortbound.least_sort_bytes`` of the record count and width the job hands
over in ``kernel_shapes["sort_stage"]``, over the peak HBM bandwidth of the
device kind), over the device time of the shuffle engine's wide-stage
executables (``jit_run``) per job, per device, in %."""
from benchmarks.chip import roofline, sortbound, xtrace


def is_wide_stage(name: str) -> bool:
    return name.startswith("jit_run(")


def read(run):
    shape = run.kernel_shapes.get("sort_stage")
    ms = xtrace.op_ms_per_job(run, is_wide_stage, line="modules")
    if not shape or not ms:
        return None
    nbytes = sortbound.least_sort_bytes(shape["records"], shape["record_bytes"])
    least, _ = roofline.least_seconds(nbytes / run.devices, 0.0,
                                      roofline.peaks(run.device_kind))
    return 100.0 * least / (ms / 1e3)
