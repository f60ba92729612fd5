"""The reduction from a profiler trace to the per-layer metrics, on a small
synthetic trace, and the reading of a real (CPU) ``.xplane.pb``."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import harness, roofline, xtrace
from benchmarks.chip.xtrace import Span, Trace

MS = 1_000_000


def _trace():
    # window 0..100 ms; device 0: sort 10-40, fusion 30-50 (overlap), all-to-all
    # 60-70; device 1 busy 0-20 only. Host: build 0-5, wait 5-95.
    tr = Trace()
    tr.ops[0] = [Span("sort.1", 10 * MS, 40 * MS), Span("fusion.7", 30 * MS, 50 * MS),
                 Span("all-to-all.3", 60 * MS, 70 * MS)]
    tr.ops[1] = [Span("sort.1", 0, 20 * MS)]
    tr.host = [Span("bench:window", 0, 100 * MS), Span("bench:build", 0, 5 * MS),
               Span("bench:wait", 5 * MS, 95 * MS)]
    return tr


def _run(tr, devices=2, jobs=2, **kw):
    fields = dict(cell="c", window_s=0.1, jobs=jobs, records=jobs * 1000,
                  setup_s=1.0, memory_peak_bytes=0, compiles=0, counters={},
                  kernel_shapes={}, device_kind="TPU v5 lite", trace=tr,
                  window_ns=(0, 100 * MS), devices=devices)
    fields.update(kw)
    return harness.Run(**fields)


def test_interval_arithmetic():
    tr = _trace()
    assert xtrace.busy_ns(tr.ops[0], 0, 100 * MS) == 50 * MS
    assert xtrace.gaps(tr.ops[0], 0, 100 * MS) == [
        (0, 10 * MS), (50 * MS, 60 * MS), (70 * MS, 100 * MS)]
    assert xtrace.busy_ns(tr.ops[0], 35 * MS, 65 * MS) == 20 * MS
    assert xtrace.op_family("all-to-all.3") == "all-to-all"
    assert xtrace.host_state(tr.host, 80 * MS) == "wait"
    assert xtrace.host_state(tr.host, 2 * MS) == "build"


def test_idle_share_and_op_time():
    run = _run(_trace())
    # device 0 busy 50 ms, device 1 busy 20 ms: mean idle 65 %
    assert xtrace.idle_share(run) == pytest.approx(65.0)
    sort = harness.load_module("metrics", "sort_ms_per_job.dataflow").read
    # (30 + 20) ms of sort over 2 devices and 2 jobs
    assert sort(run) == pytest.approx(12.5)
    a2a = harness.load_module("metrics", "all_to_all_ms_per_job").read
    assert a2a(run) == pytest.approx(2.5)
    assert a2a(_run(Trace(ops={0: [Span("sort.1", 0, MS)]}))) is None


def test_breakdown_names_gaps_by_host_state():
    b = xtrace.breakdown(_trace(), 0, 100 * MS)
    assert b["device_ops"][0] == ["sort", pytest.approx(0.05)]
    assert b["idle_gaps"][0] == ["wait", pytest.approx(0.03)]
    assert len(b["idle_gaps"]) == 3


def test_counter_and_span_readers():
    bpr = harness.load_module("metrics", "exchange_bytes_per_record").read
    assert bpr(_run(None, counters={"shuffle/bytes_moved": 26000})) == pytest.approx(13.0)
    assert bpr(_run(None)) is None
    from repro.profile.spans import Span as TSpan

    lw = harness.load_module("metrics", "lock_wait_ms_per_job").read
    spans = [TSpan("lock_wait", "sched", 0.0, 0.004, 1, {}),
             TSpan("count", "task", 0.004, 0.01, 1, {})]
    assert lw(_run(None, tracer_spans=spans)) == pytest.approx(2.0)
    assert lw(_run(None)) is None


def test_roofline_bytes_and_peaks():
    assert roofline.segment_reduce_bytes(1 << 20, 1, 4) == 12 * (1 << 20)
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = roofline.least_seconds(819e9, 1.0, peak)
    assert (t, bound) == (pytest.approx(1.0), "hbm")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_load_reads_a_real_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(4096)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("bench:wait"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xtrace.load(xtrace.find_xplane(str(tmp_path)))
    win = tr.window()
    assert win is not None and win.dur > 0
    assert [s.name for s in tr.host if s.name != "bench:window"] == ["bench:wait"]
    # the CPU backend has no device plane: device readers find nothing
    run = _run(tr, window_ns=(win.start, win.end))
    if not tr.ops:
        assert xtrace.idle_share(run) is None
