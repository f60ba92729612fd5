"""The collectives layer's reader on a small synthetic trace: the exchange's
all-to-all ops under the name HLO text gives them and the one a v5e trace
gives them."""
import pytest

from benchmarks.chip import harness
from benchmarks.chip.test_chipbench_trace import MS, _run
from benchmarks.chip.xtrace import Span, Trace


@pytest.mark.parametrize("op", ["all-to-all.3", "all_to_all.3", "all_to_all"])
def test_all_to_all_reader_takes_either_name(op):
    a2a = harness.load_module("metrics", "all_to_all_ms_per_job.dataflow").read
    tr = Trace(ops={0: [Span("sort.1", 0, 10 * MS), Span(op, 60 * MS, 70 * MS)],
                    1: [Span(op, 10 * MS, 16 * MS)]})
    # (10 + 6) ms over 2 devices and 2 jobs
    assert a2a(_run(tr)) == pytest.approx(4.0)
    assert a2a(_run(Trace(ops={0: [Span("sort.1", 0, MS)]}))) is None
