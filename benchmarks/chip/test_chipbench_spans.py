"""The readers of the program's own spans (``progspans``) on a synthetic
trace, and the program's ``ignis:`` spans in a real (CPU) ``.xplane.pb`` of
a tiny Fig. 12 job: on the profiler's clock, inside the benchmark's job."""
import glob

import jax
import pytest

from benchmarks.chip import harness, progspans
from benchmarks.chip.test_chipbench_jobs import SEED, TINY
from benchmarks.chip.xtrace import Span, Trace
from repro.profile.spans import Span as TSpan

MS = 1_000_000
EPOCH = 1_790_000_000.0  # the profile's start on the host clock, in s


def _buf(name, cat, a_ms, b_ms, **args):
    """A buffer span from a to b ms after the profile's start."""
    return TSpan(name, cat, EPOCH + a_ms / 1e3, EPOCH + b_ms / 1e3, 1, args)


def _trace():
    # window 0..100 ms, two jobs; device 0 busy 0-20, 30-40, 60-70, 80-95:
    # idle 20-30, 40-60, 70-80 and 95-100
    tr = Trace()
    tr.ops[0] = [Span("sort.1", 0, 20 * MS), Span("fusion.2", 30 * MS, 40 * MS),
                 Span("fusion.3", 60 * MS, 70 * MS), Span("while.4", 80 * MS, 95 * MS)]
    tr.host = [Span("bench:window", 0, 100 * MS),
               Span("bench:job", 0, 50 * MS), Span("bench:wait", 10 * MS, 49 * MS),
               Span("bench:job", 50 * MS, 100 * MS), Span("bench:wait", 60 * MS, 99 * MS)]
    return tr


def _spans():
    return [
        _buf("lock_wait", "sched", 11, 12, kind="action"),
        _buf("countByValue(reduceByKey#3)", "task", 12, 48, kind="action"),
        _buf("compile:vmap", "engine", 22, 28),          # 6 ms of the 20-30 gap
        _buf("fetch:countByValue", "action", 40, 45),     # a wait: not host work
        _buf("collect:countByValue", "action", 45, 55),   # 10 ms of the 40-60 gap
        _buf("countByValue(reduceByKey#9)", "task", 61, 99, kind="action",
             task="countByValue(reduceByKey#9)"),
        _buf("collect:countByValue", "action", 70, 80),   # the whole 70-80 gap
    ]


def _run(tr=None, spans=None, counters=None, jobs=2):
    return harness.Run(
        cell="fig12-cg.1chip", window_s=0.1, jobs=jobs, records=jobs, setup_s=1.0,
        memory_peak_bytes=0, compiles=0, counters=counters or {}, kernel_shapes={},
        device_kind="TPU v5 lite", trace=_trace() if tr is None else tr,
        window_ns=(0, 100 * MS), tracer_spans=_spans() if spans is None else spans,
        devices=1)


def _reader(name):
    return harness.load_module("metrics", name).read


def test_offset_puts_buffer_spans_on_the_trace_clock():
    run = _run()
    # the last program span ends as the last job's bench:wait does
    off = progspans.offset_ns(run)
    assert off == pytest.approx(-EPOCH * 1e9, abs=1000)
    spans = progspans.on_trace_clock(run.tracer_spans, off)
    assert [s.name for s in spans][:2] == ["lock_wait", "task:action"]
    assert spans[2].start == pytest.approx(22 * MS, abs=1000)


def test_host_bound_idle_reads_the_idle_time_inside_host_work(capsys):
    run = _run()
    # (6 + 10 + 10) ms over 2 jobs; fetch: and task spans do not count
    assert _reader("host_bound_idle_ms_per_job.hybrid")(run) == pytest.approx(13.0, abs=1e-3)
    assert "wait/collect:countByValue" in capsys.readouterr().err


def test_gap_labels_name_the_innermost_program_span():
    labels = progspans.gap_labels(_run())
    assert [name for name, _ in labels] == [
        "job/collect:countByValue",   # 40-60: bench:job of the second job
        "wait/compile:vmap",          # 20-30
        "wait/collect:countByValue",  # 70-80
        "wait/task:action@countByValue",  # 95-100: only the task is open
    ]
    assert labels[0][1] == pytest.approx(0.02)
    # all 45 ms of idle split by the innermost span at each instant, per
    # job: collect 10 + 10, task 2 + 2 (then 4 in the named task), compile
    # 6, fetch 5, none 5 + 1
    split = progspans.idle_by_span(_run())
    expect = {"collect:countByValue": 10.0, "task:action": 2.0, "none": 3.0,
              "compile:vmap": 3.0, "fetch:countByValue": 2.5,
              "task:action@countByValue": 2.0}
    assert split == pytest.approx(expect, abs=1e-3)
    # a gap that no program span covers keeps the bare state
    bare = progspans.gap_labels(_run(spans=[_buf("collect:x", "action", 61, 99)]))
    assert bare[0][0] == "job"


def test_collect_and_jit_miss_readers():
    assert _reader("collect_ms_per_job.hybrid")(_run()) == pytest.approx(10.0, abs=1e-3)
    counters = {"stages/plan_cache_misses": 0, "stages/vmap_misses": 2,
                "shuffle/wide_plan_misses": 1, "coll/coll_plan_misses": 1}
    assert _reader("jit_misses_per_job.hybrid")(_run(counters=counters)) == 2.0


def test_readers_find_nothing_in_a_program_without_the_spans():
    # a program with task phases built from perf_counter stamps, no
    # host-work spans and no vmap_misses counter
    old = [TSpan("lock_wait", "sched", 5.0, 5.001, 1, {}),
           TSpan("count(map#1)", "task", 5.001, 5.01, 1, {}),
           TSpan("compute", "task", 5.001, 5.01, 1, {})]
    run = _run(spans=old, counters={"stages/plan_cache_misses": 0})
    for name in ("host_bound_idle_ms_per_job.hybrid", "collect_ms_per_job.hybrid",
                 "jit_misses_per_job.hybrid"):
        assert _reader(name)(run) is None
    assert _reader("host_bound_idle_ms_per_job.hybrid")(_run(tr=Trace())) is None


def test_program_spans_in_a_real_trace(tmp_path):
    """A tiny Fig. 12 job under jax.profiler: the task, compile and collect
    spans reach the host plane as ``ignis:*``, inside ``bench:job``, at the
    intervals of their buffer copies."""
    from jax.profiler import ProfileData

    from repro.core import Ignis
    from repro.profile.tracer import JobTracer

    spec = harness.load_spec()
    cell = harness.Cell.find(spec, "fig12-cg.1chip")
    Ignis.start()
    job = harness.load_module("jobs", "fig12_cg").Job(
        {**cell.config, **TINY}, cell.traffic, SEED, 1)
    job.setup()
    job.run_one()
    tracer = job.tracer = JobTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:job"):
        job.run_one()
    jax.profiler.stop_trace()
    tracer.detach()
    job.release()

    pd = ProfileData.from_file(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                                         recursive=True)[0])
    env = pd.find_plane_with_name("Task Environment")
    start = int(dict(env.stats)["profile_start_time"])
    events, bench = [], None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ignis:"):
                    events.append((ev.name[len("ignis:"):], ev.start_ns, ev.end_ns))
                elif ev.name == "bench:job":
                    bench = (ev.start_ns, ev.end_ns)
    kinds = {name.split(":")[0] for name, _, _ in events}
    assert {"task", "compile", "collect", "fetch", "compute", "lock_wait"} <= kinds
    assert all(bench[0] <= a <= b <= bench[1] for _, a, b in events)

    copies = sorted((progspans.ignis_name(s), round(s.t0 * 1e9), round(s.t1 * 1e9))
                    for s in tracer.spans())
    traced = sorted((name, a + start, b + start) for name, a, b in events)
    assert [c[0] for c in copies] == [t[0] for t in traced]
    for (_, a, b), (_, ta, tb) in zip(copies, traced):
        assert abs(a - ta) < 1_000_000 and abs(b - tb) < 1_000_000
