"""The lazy job layer (core/job.py, docs/driver.md): eager actions as
future facades, cross-worker job DAGs (dataflow + native + importData),
async overlap of independent branches, native nodes as lineage citizens,
call_partitions lineage repair, and early-exit take."""
import gc
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ICluster, IProperties, IWorker
from repro.core.dag import DagEngine
from repro.core.job import IJob, default_scheduler
from repro.core.native import ignis_export


@pytest.fixture
def cluster():
    return ICluster(IProperties())


@pytest.fixture
def worker(cluster):
    return IWorker(cluster, "python")


# ---------------------------------------------------------------------------
# eager actions are facades over the future API
# ---------------------------------------------------------------------------


def test_eager_actions_are_future_facades(worker):
    df = worker.parallelize(np.arange(20, dtype=np.int32)).map(lambda x: x + 1)
    s0 = default_scheduler().stats["tasks_submitted"]
    assert df.count() == df.count_async().result() == 20
    assert int(df.reduce(lambda a, b: a + b)) == int(
        df.reduce_async(lambda a, b: a + b).result()
    )
    assert [int(x) for x in df.collect()] == [
        int(x) for x in df.collect_async().result()
    ]
    assert int(df.max()) == int(df.max_async().result()) == 20
    assert int(df.min()) == int(df.min_async().result()) == 1
    assert [int(x) for x in df.take(3)] == [int(x) for x in df.take_async(3).result()]
    # the eager calls above really routed through the scheduler
    assert default_scheduler().stats["tasks_submitted"] >= s0 + 12


def test_future_protocol(worker):
    df = worker.parallelize(np.arange(8, dtype=np.int32))
    fut = df.count_async()
    assert fut.result(10) == 8
    assert fut.done() and fut.exception() is None
    seen = []
    fut.add_done_callback(lambda t: seen.append(t.state))  # already resolved
    assert seen == ["done"]


# ---------------------------------------------------------------------------
# scheduling: out-of-order resolution and overlap
# ---------------------------------------------------------------------------


def test_futures_resolve_out_of_submission_order(cluster):
    @ignis_export("slow_identity")
    def slow_identity(ctx, data=None, valid=None):
        time.sleep(0.4)
        return data, valid

    w1 = IWorker(cluster, "spmd", name="slow-w")
    w2 = IWorker(cluster, "python", name="fast-w")
    order = []
    job = IJob("ooo")
    fa = w1.call(
        "slow_identity", w1.parallelize(np.arange(8, dtype=np.int32))
    ).count_async(job=job)
    fb = w2.parallelize(np.arange(8, dtype=np.int32)).count_async(job=job)
    fa.add_done_callback(lambda t: order.append("first-submitted"))
    fb.add_done_callback(lambda t: order.append("second-submitted"))
    assert fb.result(10) == 8
    assert fa.result(10) == 8
    assert order[0] == "second-submitted"  # resolved before the slow branch


def test_independent_jobs_on_different_workers_overlap(cluster):
    @ignis_export("sleepy_identity")
    def sleepy_identity(ctx, data=None, valid=None):
        time.sleep(0.3)
        return data, valid

    w1, w2 = IWorker(cluster, "spmd"), IWorker(cluster, "spmd")
    d1 = w1.call("sleepy_identity", w1.parallelize(np.arange(4, dtype=np.int32)))
    d2 = w2.call("sleepy_identity", w2.parallelize(np.arange(4, dtype=np.int32)))
    # warm both pipelines (jit compiles) so the timed window isolates overlap
    t0 = time.perf_counter()
    assert d1.count() == 4
    t1 = time.perf_counter()
    assert d2.count() == 4
    eager_sum = time.perf_counter() - t0
    assert min(t1 - t0, eager_sum - (t1 - t0)) >= 0.3  # each stage sleeps
    t0 = time.perf_counter()
    f1 = d1.count_async(job=IJob("left"))
    f2 = d2.count_async(job=IJob("right"))
    assert f1.result(10) == 4 and f2.result(10) == 4
    wall = time.perf_counter() - t0
    # the two 0.3 s native stages on different workers must overlap
    assert wall < 0.8 * eager_sum, f"no overlap: {wall:.3f}s vs eager {eager_sum:.3f}s"
    assert default_scheduler().stats["max_concurrent"] >= 2


def test_fanout_below_shared_dep_overlaps(cluster):
    """Dependents released by a finishing task must go back to the pool:
    two independent branches hanging off ONE shared upstream stage task
    overlap instead of serializing on the finisher's thread."""

    @ignis_export("nap_identity")
    def nap_identity(ctx, data=None, valid=None):
        time.sleep(0.4)
        return data, valid

    wd = IWorker(cluster, "python")
    w1, w2 = IWorker(cluster, "spmd"), IWorker(cluster, "spmd")
    shared = wd.parallelize(np.arange(8, dtype=np.int32)).map(lambda x: x + 1)
    b1 = w1.call("nap_identity", w1.import_data(shared))
    b2 = w2.call("nap_identity", w2.import_data(shared))
    assert b1.count() == 8 and b2.count() == 8  # warm compiles
    t0 = time.perf_counter()
    job = IJob("fanout")
    f1, f2 = b1.count_async(job=job), b2.count_async(job=job)
    assert f1.result(10) == 8 and f2.result(10) == 8
    wall = time.perf_counter() - t0
    assert wall < 0.7, f"fan-out serialized: wall={wall:.3f}s"
    # the shared upstream stage was scheduled once for both branches
    assert job.stats()["stage"] == 1


# ---------------------------------------------------------------------------
# hybrid job: dataflow + native + importData in ONE scheduled DAG
# ---------------------------------------------------------------------------


def test_hybrid_job_is_one_dag_and_matches_eager(cluster):
    @ignis_export("double_native")
    def double_native(ctx, data=None, valid=None):
        return data * jnp.int32(2), valid

    wd = IWorker(cluster, "python")
    ws = IWorker(cluster, "spmd")
    base = wd.parallelize(np.arange(32, dtype=np.int32)).map(lambda x: x + 1)
    moved = ws.import_data(base)  # cross-worker reshard
    doubled = ws.call("double_native", moved)  # native SPMD stage
    back = wd.import_data(doubled).map(lambda x: x - 1)

    exp = sorted(2 * (x + 1) - 1 for x in range(32))
    job = IJob("hybrid")
    got = sorted(int(x) for x in back.collect_async(job=job).result(60))
    assert got == exp
    # ONE scheduled job: dataflow stage + native + both reshards + action
    st = job.stats()
    assert st["tasks"] >= 5 and st["failed"] == 0
    assert st["native"] == 1 and st["reshard"] == 2 and st["actions"] == 1
    assert len(st["workers"]) == 2
    txt = job.explain()
    assert "call:double_native" in txt and "importData" in txt
    # the native node is visible in the frame's physical plan too
    assert "call:double_native" in back.explain()
    # eager run of the same lineage agrees (facade path)
    assert sorted(int(x) for x in back.collect()) == exp


def test_released_job_frees_its_blocks_without_the_cyclic_collector(cluster):
    # a resolved task hands off its dependents: no deps <-> dependents cycle
    # keeps a finished job's blocks on the device until gc runs
    @ignis_export("triple_native")
    def triple_native(ctx, data=None, valid=None):
        return data * jnp.int32(3), valid

    wd, ws = IWorker(cluster, "python"), IWorker(cluster, "spmd")
    base = wd.parallelize(np.arange(64, dtype=np.int32))
    gc.collect()
    gc.disable()
    try:
        before = len(jax.live_arrays())
        for _ in range(3):
            tripled = ws.call("triple_native", ws.import_data(base.map(lambda x: x + 1)))
            job = IJob("released")
            back = wd.import_data(tripled).map(lambda x: x - 1)
            assert back.count_async(job=job).result(60) == 64
            job.release()
            del job, tripled, back
        assert len(jax.live_arrays()) == before
    finally:
        gc.enable()


def test_shared_memo_evaluates_upstream_once(cluster):
    wd = IWorker(cluster, "python")
    ws = IWorker(cluster, "spmd")
    base = wd.parallelize(np.arange(16, dtype=np.int32)).map(lambda x: x * 3)
    imported = ws.import_data(base)
    job = IJob("memo")
    f1 = imported.count_async(job=job)
    f2 = imported.reduce_async(lambda a, b: a + b, job=job)
    assert f1.result(30) == 16
    assert int(f2.result(30)) == sum(3 * x for x in range(16))
    # the reshard and the upstream stage were scheduled once, not per action
    st = job.stats()
    assert st["reshard"] == 1 and st["stage"] == 1 and st["actions"] == 2


def test_nested_eager_action_inside_native_app(cluster):
    """A native app may invoke eager actions mid-flight: same-worker
    actions re-enter this thread's lock inline; another worker's actions
    go through the pool (no lock-order deadlock)."""

    @ignis_export("nested_actions")
    def nested_actions(ctx, data=None, valid=None):
        w = ctx.worker
        inner_same = w.parallelize(np.arange(5, dtype=np.int32)).count()
        inner_other = ctx.var("other").parallelize(
            np.arange(7, dtype=np.int32)
        ).count()
        return data + jnp.int32(inner_same + inner_other), valid

    wa, wb = IWorker(cluster, "python"), IWorker(cluster, "python")
    df = wa.call(
        "nested_actions",
        wa.parallelize(np.arange(4, dtype=np.int32)),
        other=wb,
    )
    assert sorted(int(x) for x in df.collect()) == [x + 12 for x in range(4)]
    assert default_scheduler().stats["inline_runs"] >= 1


def test_nested_cross_worker_lineage_does_not_deadlock(cluster):
    """The hard nesting case: a native app on worker A waits on a nested
    action whose lineage depends on worker B. The A-holding thread must
    cooperatively run the A-owned continuation tasks instead of parking
    (a pool thread can never take A's lock while the app holds it)."""

    @ignis_export("nested_cross")
    def nested_cross(ctx, data=None, valid=None):
        wa, wb = ctx.worker, ctx.var("other")
        inner = wa.import_data(
            wb.parallelize(np.arange(6, dtype=np.int32)).map(lambda x: x + 1)
        )
        return data + jnp.int32(inner.count()), valid

    wa, wb = IWorker(cluster, "python"), IWorker(cluster, "python")
    df = wa.call(
        "nested_cross", wa.parallelize(np.arange(4, dtype=np.int32)), other=wb
    )
    fut = df.collect_async()
    got = sorted(int(x) for x in fut.result(60))  # deadlock ⇒ TimeoutError
    assert got == [x + 6 for x in range(4)]
    assert default_scheduler().stats["helped_runs"] >= 1


def test_backed_off_frame_releases_its_own_acquire():
    """Regression (PR 6 review): ``task.lock_dropped`` describes the
    CLAIMING frame — the one whose ``_run_locked`` ran the task body and
    dropped the lock in ``_settle``. A pool thread that parked on acquire,
    won the lock only after that drop, and backed off on state != PENDING
    must still release its own acquisition: an RLock can never be released
    from another thread, so skipping here would leak the worker lock and
    block every subsequent task on that worker forever."""
    import threading

    from repro.core.job import DONE, JobScheduler, JobTask

    class W:
        pass

    w = W()
    w._job_lock = threading.RLock()
    sched = JobScheduler()
    stale = JobTask("stale", "action", w, lambda: 1, [])
    # simulate the helper frame having claimed + run the task and dropped
    # the lock in _settle while this frame was parked on acquire
    stale.state = DONE
    stale.lock_dropped = True
    sched._run(stale)  # this frame: acquire → back off → MUST release
    # symptom-level check: a follow-up task on the same worker lock runs
    follow = JobTask("follow", "action", w, lambda: 42, [])
    sched.submit(follow)
    assert follow.event.wait(10), "worker lock leaked: follow-up never ran"
    assert follow.result == 42 and follow.error is None


def test_job_wait_returns_in_submission_order(worker):
    job = IJob("waitall")
    a = worker.parallelize(np.arange(6, dtype=np.int32))
    a.count_async(job=job)
    a.reduce_async(lambda x, y: x + y, job=job)
    got = job.wait(30)
    assert got[0] == 6 and int(got[1]) == sum(range(6))


def test_full_take_feeds_the_job_memo(worker):
    """A fully-consumed lazy iterator materialises into the job's shared
    memo: a later action in the same job reuses the blocks."""
    df = worker.parallelize(np.arange(30, dtype=np.int32), blocks=3).map(
        lambda x: x + 1
    )
    job = IJob("take-then-collect")
    assert len(df.take_async(100, job=job).result(30)) == 30  # full consumption
    before = worker.engine.stats["node_computes"]
    assert len(df.collect_async(job=job).result(30)) == 30
    assert worker.engine.stats["node_computes"] == before  # memo hit, no redo


def test_future_failure_propagates(worker):
    @ignis_export("boom_app")
    def boom_app(ctx, data=None, valid=None):
        raise RuntimeError("kaboom")

    df = worker.call("boom_app", worker.parallelize(np.arange(4, dtype=np.int32)))
    fut = df.count_async()
    with pytest.raises(RuntimeError, match="kaboom"):
        fut.result(10)
    assert fut.done() and isinstance(fut.exception(), RuntimeError)


# ---------------------------------------------------------------------------
# native apps as first-class lineage citizens
# ---------------------------------------------------------------------------


def test_void_call_routes_through_dag(worker):
    hits = []

    @ignis_export("probe_void")
    def probe_void(ctx, data=None, valid=None):
        hits.append(int(ctx.var("x")))

    assert worker.void_call("probe_void", x=7) is None  # eager facade
    assert hits == [7]
    fut = worker.void_call_async("probe_void", x=9)
    assert fut.result(10) is None
    assert hits == [7, 9]
    assert fut.task.kind == "action"
    # the app itself ran as a native task in the job DAG, not eagerly outside
    s = default_scheduler().stats
    assert s["tasks_completed"] >= 2


def test_void_call_receives_dataframe(worker):
    sums = []

    @ignis_export("sum_void")
    def sum_void(ctx, data=None, valid=None):
        sums.append(int(jnp.where(valid, data, 0).sum()))

    df = worker.parallelize(np.arange(10, dtype=np.int32)).map(lambda x: x * 2)
    worker.void_call("sum_void", df)
    assert sums == [2 * sum(range(10))]


def test_native_ctx_binds_at_execution_time(worker):
    seen = {}

    @ignis_export("read_knob")
    def read_knob(ctx, data=None, valid=None):
        seen["v"] = ctx.var("knob")
        return data, valid

    df = worker.call("read_knob", worker.parallelize(np.arange(4, dtype=np.int32)))
    worker.context.set_var("knob", 123)  # after definition, before execution
    df.count()
    assert seen["v"] == 123  # stale eager-bound ctx would have seen None


def test_native_params_digest_in_sig(worker):
    @ignis_export("sig_app")
    def sig_app(ctx, data=None, valid=None):
        return data, valid

    df = worker.parallelize(np.arange(4, dtype=np.int32))
    a = worker.call("sig_app", df, knob=1)
    b = worker.call("sig_app", df, knob=2)
    c = worker.call("sig_app", df, knob=1)
    assert a.node.sig != b.node.sig  # params are part of the signature
    assert a.node.sig == c.node.sig  # re-built identical call keys the same


def test_call_partitions_preserves_blocks_and_repairs(worker):
    calls = []

    @ignis_export("scale_blocks")
    def scale_blocks(ctx, data=None, valid=None):
        calls.append(1)
        return data * jnp.int32(int(ctx.var("k", 2))), valid

    df = worker.parallelize(np.arange(40, dtype=np.int32), blocks=4)
    out = worker.call_partitions("scale_blocks", df, k=5).persist()
    assert sorted(int(x) for x in out.collect()) == [x * 5 for x in range(40)]
    assert len(out.node.result) == 4  # partition-preserving: no _merged collapse
    assert len(calls) == 4  # app ran once per block
    base = worker.engine.stats["block_recomputes"]
    DagEngine.kill_block(out.node, 2)
    assert sorted(int(x) for x in out.collect()) == [x * 5 for x in range(40)]
    assert worker.engine.stats["block_recomputes"] - base == 1  # lost block only
    assert len(calls) == 5  # the app re-ran for exactly one block


def test_planning_stops_at_materialised_nodes(worker):
    """A persisted node shields its ancestors: scheduling an action above it
    must not re-execute an upstream native app (side effects run once)."""
    calls = []

    @ignis_export("count_calls")
    def count_calls(ctx, data=None, valid=None):
        calls.append(1)
        return data, valid

    src = worker.parallelize(np.arange(12, dtype=np.int32))
    cached = worker.call("count_calls", src).map(lambda x: x + 1).persist()
    assert cached.count() == 12 and len(calls) == 1
    job = IJob("above-cache")
    assert cached.filter(lambda x: x > 0).count_async(job=job).result(30) == 12
    assert len(calls) == 1  # the native app did NOT re-run
    assert job.stats()["native"] == 0  # and was never scheduled


def test_boundary_with_killed_block_repairs_on_owner(cluster):
    """A cached native node that lost a block is NOT materialised: its
    owner's engine repairs it as a scheduled task under the owner's lock."""

    @ignis_export("ident_blocks")
    def ident_blocks(ctx, data=None, valid=None):
        return data, valid

    wa, wb = IWorker(cluster, "python"), IWorker(cluster, "python")
    df = wa.parallelize(np.arange(20, dtype=np.int32), blocks=2)
    sc = wa.call_partitions("ident_blocks", df).persist()
    assert sc.count() == 20
    DagEngine.kill_block(sc.node, 1)
    job = IJob("repair")
    assert wb.import_data(sc).count_async(job=job).result(30) == 20
    owners = [t.worker for t in job.tasks if t.kind == "native"]
    assert owners == [wa]  # repair task ran on the owning worker


def test_void_call_param_named_job_reaches_app(worker):
    """Eager void_call keeps the unrestricted param namespace: a param
    literally named "job" must reach the app's context, not be swallowed
    by the async path's job= keyword."""
    seen = {}

    @ignis_export("job_param_app")
    def job_param_app(ctx, data=None, valid=None):
        seen["job"] = ctx.var("job")

    worker.void_call("job_param_app", job="nightly")
    assert seen["job"] == "nightly"


def test_call_partitions_composes_with_downstream_ops(worker):
    @ignis_export("inc_blocks")
    def inc_blocks(ctx, data=None, valid=None):
        return data + jnp.int32(1), valid

    df = worker.parallelize(np.arange(20, dtype=np.int32), blocks=2)
    out = worker.call_partitions("inc_blocks", df).map(lambda x: x * 10)
    assert sorted(int(x) for x in out.collect()) == [(x + 1) * 10 for x in range(20)]
    assert "callPartitions:inc_blocks" in out.explain()


# ---------------------------------------------------------------------------
# early-exit take
# ---------------------------------------------------------------------------


def test_take_early_exits(worker):
    df = worker.parallelize(np.arange(40, dtype=np.int32), blocks=4).map(
        lambda x: x * 2
    )
    assert [int(x) for x in df.take(5)] == [0, 2, 4, 6, 8]
    # only the first of 4 blocks materialised through the lazy iterator
    assert worker.engine.stats["iter_block_computes"] == 1
    assert df.take(100) == df.collect()  # over-ask degrades to collect


def test_take_keeps_stage_fusion(worker):
    """The lazy iterator routes fusable chains through the same compiled
    stage kernels (and plan cache) as full evaluation — early exit does not
    degrade a fused chain to per-op Python dispatch."""
    df = (
        worker.parallelize(np.arange(40, dtype=np.int32), blocks=4)
        .map(lambda x: x * 2)
        .filter(lambda x: x % 3 == 0)
        .map(lambda x: x + 1)
    )
    m0 = worker.engine.stats["plan_cache_misses"]
    got = [int(x) for x in df.take(5)]
    assert got == [2 * x + 1 for x in range(40) if (2 * x) % 3 == 0][:5]
    st = worker.engine.stats
    assert st["plan_cache_misses"] == m0 + 1  # the fused kernel compiled once
    # 5 rows need 2 of the 4 blocks (filter keeps 4 rows/block): 2 dispatches
    assert st["iter_block_computes"] == 2
    fs0 = st["fused_stages"]
    assert [int(x) for x in df.take(100)] == [
        2 * x + 1 for x in range(40) if (2 * x) % 3 == 0
    ]
    assert worker.engine.stats["fused_stages"] == fs0 + 1  # full pass, fused


def test_take_on_wide_lineage_falls_back_to_full_eval(worker):
    vals = np.array([5, 3, 9, 1, 7, 2], np.int32)
    got = [int(x) for x in worker.parallelize(vals).sort().take(3)]
    assert got == [1, 2, 3]


def test_take_respects_cached_nodes(worker):
    df = worker.parallelize(np.arange(30, dtype=np.int32), blocks=3)
    mid = df.map(lambda x: x + 1).persist()
    assert [int(x) for x in mid.take(4)] == [1, 2, 3, 4]
    assert mid.node.result is not None  # cache still populated (full eval)
