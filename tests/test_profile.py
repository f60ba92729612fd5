"""The introspection API (docs/profiling.md, DESIGN.md §13): trace-schema
validation, replay determinism, facade equivalence over the unified metrics
tree, the typed property registry, and the two cost-model decisions
(cost-aware fusion boundaries, auto speculative timeouts)."""
import gc
import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICluster, IProperties, IWorker
from repro.core.job import IJob, task_history_key
from repro.core.metrics import Counters, MetricsTree
from repro.profile import (
    CostModel,
    Hypothesis,
    JobTracer,
    Span,
    TaskRecord,
    Trace,
    TraceBuffer,
    capture,
    predicted_vs_measured,
    simulate,
    to_chrome,
    validate,
)
from repro.profile import spans


@pytest.fixture
def cluster():
    return ICluster(IProperties())


@pytest.fixture
def worker(cluster):
    return IWorker(cluster, "python")


def _traced_run(worker, n_actions=3):
    """Run a few actions under an attached tracer; return (job, tracer)."""
    tracer = JobTracer()
    tracer.attach_worker(worker)
    job = IJob("traced")
    tracer.attach(job)
    df = worker.parallelize(np.arange(64, dtype=np.int32)).map(lambda x: x + 1)
    futs = [df.count_async(job=job) for _ in range(n_actions)]
    for f in futs:
        assert f.result() == 64
    return job, tracer


# ---------------------------------------------------------------------------
# trace schema
# ---------------------------------------------------------------------------


def test_chrome_trace_validates_clean(worker, tmp_path):
    job, tracer = _traced_run(worker)
    trace = tracer.to_chrome()
    assert validate(trace) == []
    # spans exist and carry lane labels in args (tid is the thread)
    task_events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert task_events
    assert all("lane" in e["args"] for e in task_events
               if e.get("cat") in ("task", "sched"))
    # round-trips through JSON on disk
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    assert validate(json.loads(path.read_text())) == []
    tracer.detach()


def test_validate_flags_negative_duration():
    bad = to_chrome([Span("t", "task", 2.0, 1.0, 1, {"lane": "w"})])
    # the exporter clamps dur, so corrupt the event directly
    bad["traceEvents"][-1]["dur"] = -5.0
    assert any("negative dur" in p for p in validate(bad))


def test_validate_flags_non_nesting_overlap():
    spans = [
        Span("a", "task", 0.0, 1.0, 7, {}),
        Span("b", "task", 0.5, 1.5, 7, {}),  # overlaps a on the same tid
    ]
    assert any("overlaps" in p for p in validate(to_chrome(spans)))
    # same spans on different tids are fine
    ok = [Span("a", "task", 0.0, 1.0, 7, {}),
          Span("b", "task", 0.5, 1.5, 8, {})]
    assert validate(to_chrome(ok)) == []


def test_validate_rejects_malformed_container():
    assert validate({}) == ["traceEvents missing or not a list"]


def test_trace_lanes_match_explain_groups(cluster):
    """Gang-task spans carry the gang group's label — the same string
    job.explain() prints as group=."""
    w = IWorker(cluster, "python")
    g = w.groups(1)[0]
    tracer = JobTracer()
    job = IJob("gang", group=g)
    tracer.attach(job)
    df = w.parallelize(np.arange(32, dtype=np.int32))
    assert df.count_async(job=job).result() == 32
    lanes = {s.args.get("lane") for s in tracer.spans() if s.cat == "task"}
    assert g.label() in lanes


def test_tracer_summary_and_profile_mount(worker):
    job, tracer = _traced_run(worker)
    summ = tracer.summary()
    assert summ["tasks"] >= 3
    assert summ["makespan_ms"] > 0
    assert summ["cost"]["tasks_observed"] >= 3
    # attach_worker mounted the profile/ namespace on the worker tree,
    # and attach() mounts it on the job tree
    assert worker.metrics("profile")["tasks"] == summ["tasks"]
    assert job.metrics("profile")["tasks"] == summ["tasks"]
    tracer.detach()


# ---------------------------------------------------------------------------
# live spans: one span() API, on the profiler's clock
# ---------------------------------------------------------------------------


def test_span_without_a_tracer_is_the_shared_noop(monkeypatch):
    gc.collect()  # tracers that earlier tests dropped without detach() go off
    assert not spans._on

    def no_jax(*a, **kw):
        raise AssertionError("span() called into JAX with tracing off")

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", no_jax)
    buf = TraceBuffer()
    s = spans.span("stage:map", op="map", into=buf)
    assert s is spans.span("collect:count", "action") is spans._NOOP
    with s:
        pass
    fn = lambda x: x  # noqa: E731
    assert spans.first_call("vmap", fn) is fn
    assert len(buf) == 0


def test_span_is_live_while_a_tracer_is_attached():
    gc.collect()
    before = spans._tracers
    tracer = JobTracer()
    tracer.attach(IJob("live"))
    assert spans._on and spans._tracers == before + 1
    with spans.span("outer", into=tracer.buffer, k=1):
        with spans.span("inner", "action"):  # inherits the buffer
            pass
    with spans.span("stray"):  # no span around it on this thread: nowhere
        pass
    got = tracer.spans()
    assert [(s.name, s.cat) for s in got] == [("inner", "action"), ("outer", "engine")]
    inner, outer = got
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 and inner.tid == outer.tid
    assert outer.args == {"k": 1}
    # buffer spans are stamped on the profiler's host clock (CLOCK_REALTIME)
    assert abs(outer.t1 - spans.clock()) < 60.0
    tracer.detach()
    assert spans._tracers == before


def test_spans_from_many_threads_all_land():
    """Threads opening spans into one buffer at once lose none, and each
    thread's nested spans inherit its own binding."""
    import sys
    import threading

    tracer = JobTracer()
    tracer.attach(IJob("threads"))
    n_threads, n_spans = 12, 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_spans):
                with spans.span("outer", into=tracer.buffer):
                    with spans.span("inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    got = tracer.spans()
    assert len(got) == 2 * n_threads * n_spans
    assert sum(s.name == "inner" for s in got) == n_threads * n_spans
    assert validate(tracer.to_chrome()) == []
    tracer.detach()


def test_engine_and_action_spans_nest_in_their_task(worker):
    tracer = JobTracer()
    tracer.attach_worker(worker)
    job = IJob("engine")
    tracer.attach(job)
    keys = np.arange(64, dtype=np.int32) % 8
    df = worker.parallelize(keys).map(lambda x: x + 1).map(lambda x: x * 2)
    got = df.count_by_value_async(job=job).result()
    assert got == {int(k): 8 for k in (np.arange(8) + 1) * 2}
    recorded = tracer.spans()
    names = {s.name for s in recorded}
    assert {"lock_wait", "compute", "settle", "stage:map", "wide:reduceByKey",
            "compile:stage", "compile:wide", "fetch:countByValue",
            "collect:countByValue"} <= names
    task = next(s for s in recorded if s.cat == "task"
                and s.name.startswith("countByValue("))
    assert task.args["job"] == "engine" and task.args["kind"] == "action"
    assert task.args["state"] == "done" and "lane" in task.args
    inside = [s for s in recorded if s.cat in ("engine", "action")]
    assert all(task.t0 <= s.t0 <= s.t1 <= task.t1 and s.tid == task.tid
               for s in inside)
    assert all(s.args["time"] == "host dispatch" for s in inside
               if s.name.startswith(("stage:", "wide:")))
    assert validate(tracer.to_chrome()) == []
    tracer.detach()


def test_compile_spans_cover_the_first_call_after_a_miss(worker):
    tracer = JobTracer()
    job = IJob("misses")
    tracer.attach(job)
    src = worker.parallelize(np.arange(32, dtype=np.int32))
    fn = lambda x: x + 3  # noqa: E731
    misses = worker.metrics("stages")["vmap_misses"]
    assert src.map(fn).count_async(job=job).result() == 32
    assert worker.metrics("stages")["vmap_misses"] == misses + 1
    assert [s.name for s in tracer.spans()].count("compile:vmap") == 1
    # a rebuilt lineage over the same function hits the cache: no compile
    assert src.map(fn).count_async(job=job).result() == 32
    assert worker.metrics("stages")["vmap_misses"] == misses + 1
    assert [s.name for s in tracer.spans()].count("compile:vmap") == 1
    tracer.detach()
    # the counter counts untraced misses too
    assert src.map(lambda x: x - 3).count() == 32
    assert worker.metrics("stages")["vmap_misses"] == misses + 2


# ---------------------------------------------------------------------------
# replay: determinism + semantics
# ---------------------------------------------------------------------------


def _diamond():
    # a -> (b, c) -> d, b and c on different lanes
    return Trace(tasks=(
        TaskRecord(0, "a", "stage", "w0", 1.0),
        TaskRecord(1, "b", "stage", "w0", 2.0, deps=(0,)),
        TaskRecord(2, "c", "stage", "w1", 3.0, deps=(0,)),
        TaskRecord(3, "d", "action", "w0", 1.0, deps=(1, 2)),
    ), wall_s=5.0)


def test_replay_is_deterministic():
    tr = _diamond()
    s1 = simulate(tr, Hypothesis(lanes=2))
    s2 = simulate(tr, Hypothesis(lanes=2))
    assert s1 == s2
    assert s1.order == s2.order and s1.task_times == s2.task_times


def test_replay_diamond_semantics():
    s = simulate(_diamond())
    # b and c overlap on separate lanes; d waits for the slower branch
    assert s.makespan_s == pytest.approx(1.0 + 3.0 + 1.0)
    assert s.task_times[3][0] == pytest.approx(4.0)
    assert s.order == (0, 1, 2, 3)


def test_replay_single_lane_serialises():
    s = simulate(_diamond(), Hypothesis(lanes=1))
    assert s.makespan_s == pytest.approx(1.0 + 2.0 + 3.0 + 1.0)
    assert s.lanes == ("lane0",)


def test_replay_settle_frees_lane_but_blocks_dependents():
    # a's settle tail overlaps b (same lane), but c depends on a so it
    # waits for the settle to finish — the live one-way lock drop.
    tr = Trace(tasks=(
        TaskRecord(0, "a", "stage", "w0", 1.0, settle_s=2.0),
        TaskRecord(1, "b", "stage", "w0", 1.0),
        TaskRecord(2, "c", "stage", "w1", 0.5, deps=(0,)),
    ))
    s = simulate(tr)
    assert s.task_times[1][0] == pytest.approx(1.0)   # lane free after body
    assert s.task_times[2][0] == pytest.approx(3.0)   # dep waits for settle


def test_replay_speculative_timeout_caps_straggler():
    tr = Trace(tasks=(
        TaskRecord(0, "a", "stage", "w0", 1.0),
        TaskRecord(1, "b", "stage", "w1", 50.0),  # straggler
        TaskRecord(2, "c", "stage", "w0", 1.0),
    ))
    base = simulate(tr).makespan_s
    cut = simulate(tr, Hypothesis(speculative_timeout_s=2.0)).makespan_s
    # duplicate finishes in typical(stage)=1s once the 2s deadline passes
    assert base == pytest.approx(50.0)
    assert cut == pytest.approx(3.0)


def test_replay_scale_and_price_override():
    tr = _diamond()
    assert simulate(tr, Hypothesis(scale=2.0)).makespan_s == pytest.approx(
        2 * simulate(tr).makespan_s)
    flat = simulate(tr, price=lambda t: 1.0)
    assert flat.makespan_s == pytest.approx(3.0)  # a -> max(b,c) -> d, 1s each


def test_replay_cycle_raises():
    tr = Trace(tasks=(
        TaskRecord(0, "a", "stage", "w0", 1.0, deps=(1,)),
        TaskRecord(1, "b", "stage", "w0", 1.0, deps=(0,)),
    ))
    with pytest.raises(ValueError, match="cycle"):
        simulate(tr)


def test_capture_and_identity_replay_accuracy(worker):
    job, tracer = _traced_run(worker, n_actions=4)
    tr = capture(job)
    assert len(tr.tasks) >= 4 and tr.wall_s > 0
    r = predicted_vs_measured(job)
    assert r["tasks"] == len(tr.tasks)
    # identity replay of a serial single-worker capture tracks the wall
    assert 0.0 < r["accuracy"] <= 1.0
    tracer.detach()


# ---------------------------------------------------------------------------
# metrics tree + facade equivalence
# ---------------------------------------------------------------------------


def test_counters_are_plain_dicts():
    c = Counters("demo", {"hits": 0}, docs={"hits": "cache hits"})
    c["hits"] += 2
    c["grown"] = 1  # unknown-key writes allowed
    assert dict(c) == {"hits": 2, "grown": 1}
    assert c.describe() == {"hits": "cache hits"}
    assert c.snapshot() == dict(c) and c.snapshot() is not c


def test_metrics_tree_paths_and_unknown_key():
    live = Counters("x", {"n": 1})
    tree = MetricsTree(x=live, thunk=lambda: {"v": 7})
    tree.mount("a/b", {"deep": True})
    live["n"] += 1  # mounts are live, not copies
    snap = tree.snapshot()
    assert snap["x"] == {"n": 2}
    assert snap["thunk"] == {"v": 7}
    assert tree.snapshot("a/b") == {"deep": True}
    with pytest.raises(KeyError, match="have:"):
        tree.snapshot("typo")


def test_worker_facades_equal_metrics_tree(worker):
    df = worker.parallelize(np.arange(48, dtype=np.int32))
    assert df.map(lambda x: x * 2).map(lambda x: x + 1).count() == 48
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        assert worker.stage_stats() == worker.metrics("stages")
        merged = {**worker.metrics("shuffle"), **worker.metrics("kernels"),
                  **worker.metrics("coll")}
        assert worker.shuffle_stats() == merged
    assert {"coll", "kernels", "shuffle", "stages"} <= worker.metrics().keys()


def test_job_stats_facade_equals_metrics(worker):
    job = IJob("facade")
    df = worker.parallelize(np.arange(16, dtype=np.int32))
    assert df.count_async(job=job).result() == 16
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = job.stats()
    tree = job.metrics()
    assert old["coll"] == tree["coll"]
    for k in ("tasks", "done", "failed", "wall_ms"):
        assert k in old and k in tree["tasks"]
    # facades are marked deprecated (once per process — may have fired
    # already in this run, so only check the category when present)
    assert all(issubclass(w.category, DeprecationWarning) for w in rec)


def test_old_accessors_emit_deprecation_once(worker):
    from repro.core import metrics as m
    m._warned.discard("IWorker.stage_stats()->IWorker.metrics(\"stages\")")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        worker.stage_stats()
        worker.stage_stats()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and "metrics" in str(dep[0].message)


# ---------------------------------------------------------------------------
# typed property registry
# ---------------------------------------------------------------------------


def test_unknown_ignis_key_warns_once_but_stores():
    from repro.core import properties as P
    P._warned_keys.discard("ignis.totally.unknown")  # props: ignore
    props = IProperties()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        props["ignis.totally.unknown"] = "1"  # props: ignore
        props["ignis.totally.unknown"] = "2"  # props: ignore
        props["app.private.key"] = "ok"  # non-ignis prefix: silent
    assert len([w for w in rec if "unknown property" in str(w.message)]) == 1
    assert props["ignis.totally.unknown"] == "2"  # props: ignore
    assert "unknown property 'ignis.totally.unknown'" in str(  # props: ignore
        props.validate())


def test_invalid_value_warns_but_stores():
    from repro.core import properties as P
    P._warned_keys.discard("ignis.task.attempts=lots")
    props = IProperties()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        props["ignis.task.attempts"] = "lots"
    assert any("expected an integer" in str(w.message) for w in rec)
    assert props["ignis.task.attempts"] == "lots"  # stored anyway
    assert props.get_int("ignis.task.attempts", 2) == 2  # getter absorbs
    assert any("expected an integer" in p for p in props.validate())


def test_speculative_timeout_auto_validator():
    props = IProperties()
    spec = props.describe("ignis.task.speculative.timeout")
    assert spec is not None and spec.type == "str"
    assert spec.check("auto") is None
    assert spec.check("2.5") is None
    assert spec.check("fast") is not None


def test_registry_defaults_are_valid():
    assert IProperties().validate() == []


def test_choices_enforced_in_validate():
    props = IProperties()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        props["ignis.fusion.mode"] = "greedy"
    assert any("ignis.fusion.mode" in p for p in props.validate())


# ---------------------------------------------------------------------------
# decision 1: cost-aware fusion boundaries
# ---------------------------------------------------------------------------


def test_cost_fusion_defers_then_fuses():
    f1, f2 = (lambda x: x * 2), (lambda x: x + 1)

    def build(w):
        return w.parallelize(np.arange(64, dtype=np.int32)).map(f1).map(f2)

    cl = ICluster(IProperties({"ignis.fusion.mode": "cost"}))
    w = IWorker(cl, "python")
    assert w.engine.fusion_mode == "cost" and w.engine.cost_model is not None

    assert build(w).count() == 64  # first sighting: compile unamortised
    assert w.engine.stats["fusion_deferred"] == 1
    assert w.engine.stats["fused_stages"] == 0

    assert build(w).count() == 64  # second sighting: amortised, fuse
    assert w.engine.stats["fused_stages"] == 1
    cost = w.engine.cost_model.snapshot()
    assert cost["fuse_decisions"] >= 2 and cost["fuse_deferrals"] >= 1


def test_explain_does_not_consume_sightings():
    f1, f2 = (lambda x: x * 2), (lambda x: x - 3)
    cl = ICluster(IProperties({"ignis.fusion.mode": "cost"}))
    w = IWorker(cl, "python")
    df = w.parallelize(np.arange(32, dtype=np.int32)).map(f1).map(f2)
    before = w.engine.cost_model.snapshot()["stage_signatures"]
    w.engine.explain(df.node)
    assert w.engine.cost_model.snapshot()["stage_signatures"] == before


def test_should_fuse_first_sighting_math():
    m = CostModel()
    p = m.params
    # enough blocks that one run's dispatch savings beat the compile
    big = int(2 * p.compile_s_per_op / p.dispatch_s) + 1
    assert m.should_fuse("sigA", n_ops=2, nblocks=big) is True
    assert m.should_fuse("sigB", n_ops=2, nblocks=1) is False
    assert m.should_fuse("sigB", n_ops=2, nblocks=1) is True  # 2nd sighting
    assert m.peek_fuse("sigC") is False  # peek records nothing
    assert m.should_fuse("sigC", n_ops=3, nblocks=1) is False


def test_static_mode_fuses_unconditionally(worker):
    # default mode: no deferral ever, cost model untouched by the planner
    df = worker.parallelize(np.arange(32, dtype=np.int32))
    assert df.map(lambda x: x * 2).map(lambda x: x + 1).count() == 32
    assert worker.engine.stats["fusion_deferred"] == 0
    assert worker.engine.stats["fused_stages"] >= 1


# ---------------------------------------------------------------------------
# decision 2: auto speculative timeouts
# ---------------------------------------------------------------------------


def test_auto_timeout_derives_from_history():
    m = CostModel()
    key = ("stage", "sig")
    assert m.speculative_timeout_s(key, default_s=30.0) == 30.0  # no history
    for d in (1.0, 2.0, 9.0):
        m.observe_task(key, d)
    assert m.typical_s(key) == 2.0  # median
    assert m.speculative_timeout_s(key, factor=3.0) == pytest.approx(6.0)
    # microsecond tasks: floored so jitter can't spawn duplicates
    fast = ("stage", "fast")
    m.observe_task(fast, 1e-5)
    assert m.speculative_timeout_s(fast, factor=3.0) == pytest.approx(0.05)


def test_scheduler_observes_into_engine_cost_model(worker):
    df = worker.parallelize(np.arange(32, dtype=np.int32)).map(lambda x: x + 1)
    before = worker.engine.cost_model.snapshot()["tasks_observed"]
    assert df.count() == 32
    after = worker.engine.cost_model.snapshot()["tasks_observed"]
    assert after > before


def test_task_history_key_is_structural(worker):
    job = IJob("keys")
    df = worker.parallelize(np.arange(8, dtype=np.int32)).map(lambda x: x + 1)
    assert df.count_async(job=job).result() == 8
    keys = {task_history_key(t) for t in job.tasks}
    assert keys and all(isinstance(k, tuple) and len(k) == 2 for k in keys)


def test_auto_timeout_used_by_gang_scheduler(cluster):
    """End to end: timeout=auto routes deadline computation through the
    worker engine's cost model (auto_timeouts counter moves)."""
    cluster.props["ignis.task.speculative"] = "true"
    cluster.props["ignis.task.speculative.timeout"] = "auto"
    w = IWorker(cluster, "python")
    g = w.groups(1)[0]
    before = w.engine.cost_model.snapshot()["auto_timeouts"]
    job = IJob("auto", group=g)
    df = w.parallelize(np.arange(16, dtype=np.int32))
    assert df.count_async(job=job).result() == 16
    assert w.engine.cost_model.snapshot()["auto_timeouts"] > before
