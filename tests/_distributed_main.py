"""Multi-device collective checks — run in a subprocess with 8 host devices
(tests/test_distributed.py drives this; the flag must precede jax import and
must NOT leak into the main pytest process per the dry-run ground rules).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import ICluster, IProperties, IWorker  # noqa: E402
from repro.core import comm  # noqa: E402
from repro.core import compat  # noqa: E402
from repro.distributed.pipeline import pipeline_apply, reference_apply  # noqa: E402
from repro.launch.mesh import make_local_mesh, make_pp_mesh  # noqa: E402


def check(name, ok):
    print(f"{name}: {'OK' if ok else 'FAIL'}")
    if not ok:
        raise SystemExit(1)


def main():
    assert len(jax.devices()) == 8, jax.devices()

    # ---- dataflow over 8 executors ----------------------------------------
    props = IProperties({"ignis.executor.instances": "8"})
    w = IWorker(ICluster(props), "python")
    assert w.executors == 8

    rng = np.random.default_rng(0)
    vals = rng.integers(0, 100000, 4096).astype(np.int32)
    got = [int(x) for x in w.parallelize(vals).sort().collect()]
    check("psrs_sort_8shards", got == sorted(int(v) for v in vals))

    # the PSRS stage's carrying sorts vs the argsort-plus-gather oracle: the
    # valid rows, in order, bit for bit (a valid INT32_MAX key behind invalid
    # rows included), and the leaves that still take a gather
    from repro.core import shuffle as sh
    from repro.core.partition import Block

    n = 8 * 96
    valid = rng.random(n) < 0.7
    ints = rng.integers(-50, 50, n).astype(np.int32)
    ints[rng.random(n) < 0.05] = np.iinfo(np.int32).max
    valid[:8] = False
    cases = {
        "int32_desc": (jnp.asarray(ints), lambda r: r, False, 0, 3),
        "float32_asc": (jnp.asarray((ints / 4).astype(np.float32)), lambda r: r, True, 0, 2),
        "tree_2d_leaf": ({"key": jnp.asarray(ints % 7),
                          "vec": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)},
                         lambda r: r["key"], True, 1, 3),
        "max_key_after_invalid": (jnp.asarray(ints), lambda r: r, True, 0, 2),
    }
    # the last number: leaves the PSRS exchange sends, each filled by 8
    # bucket slices (valid, the key, and every data leaf that is not it)
    for name, (data, key_fn, ascending, gathers, sent) in cases.items():
        keys = jax.vmap(key_fn)(data)
        keys = keys if ascending else -keys
        order = jnp.argsort(jnp.where(valid, keys, sh._sentinel(keys.dtype)), stable=True)
        keep = np.asarray(valid)[np.asarray(order)]
        want = jax.tree.map(lambda x: np.asarray(x[order])[keep], data)
        before = w.shuffle.stats["sort_gathers"]
        slices = w.shuffle.stats["bucket_slices"]
        out = w.shuffle.sort(("carry", name), Block(data, jnp.asarray(valid)),
                             key_fn, ascending)
        got_v = np.asarray(out.valid)
        got_d = jax.tree.map(lambda x: np.asarray(x)[got_v], out.data)
        check(f"sort_carry_8shards_{name}",
              all(g.tobytes() == x.tobytes() for g, x in
                  zip(jax.tree.leaves(got_d), jax.tree.leaves(want)))
              and w.shuffle.stats["sort_gathers"] - before == gathers
              and w.shuffle.stats["bucket_slices"] - slices == 8 * sent)

    # tuple keys on 8 shards (PSRS samples, pivots and routing compared
    # lexicographically) against the NumPy reference of the CPU tests: ties
    # in the leading leaves, each dtype's largest value, invalid rows, a
    # 2-D and a 1-D payload leaf, both orders
    from test_shuffle_engine import TUPLE_KEYS, _records, check_tuple_sort

    def tuple_sort_ok(worker, name, leaves, ascending, n):
        data, valid = _records(n=n, seed=leaves)
        out = worker.shuffle.sort(("tuple", name), Block(
            jax.tree.map(jnp.asarray, data), jnp.asarray(valid)),
            TUPLE_KEYS[leaves], ascending)
        try:
            check_tuple_sort(data, valid, out.data, out.valid, leaves, ascending)
        except AssertionError:
            return False
        return True

    for leaves in (2, 3):
        for ascending in (True, False):
            check(f"tuple_sort_8shards_{leaves}_{'asc' if ascending else 'desc'}",
                  tuple_sort_ok(w, f"{leaves}{ascending}", leaves, ascending, 8 * 96))
    for name, x in {"int32_min": np.asarray([3, -2**31, 0, 2**31 - 1, -1] * 40, np.int32),
                    "uint32": np.asarray([0, 2**32 - 1, 5, 2**31] * 50, np.uint32)}.items():
        got = [np.asarray(v).item() for v in w.parallelize(x).sort(ascending=False).collect()]
        check(f"descending_8shards_{name}", got == sorted(x.tolist(), reverse=True))

    # float keys in the sort's order on 8 shards: -0 equals 0, and NaN sorts
    # after every other value in both orders (descending negates the key),
    # with NaN rows on every executor and NaN pivots; alone, and as the first
    # of two leaves with invalid rows, which must stay behind the NaN rows
    f = rng.integers(-20, 20, n).astype(np.float32) / 4
    f[rng.random(n) < 0.3] = np.nan
    f[rng.random(n) < 0.1] = -0.0
    tag = np.arange(n, dtype=np.int32) % 5
    valid = rng.random(n) < 0.8
    for ascending in (True, False):
        word = "asc" if ascending else "desc"
        got = np.asarray([np.asarray(v).item()
                          for v in w.parallelize(f).sort(ascending=ascending).collect()])
        want = np.sort(f) if ascending else -np.sort(-f)
        check(f"float_nan_sort_8shards_{word}", np.array_equal(got, want, equal_nan=True))
        out = w.shuffle.sort(("float_nan", word), Block(
            {"f": jnp.asarray(f), "tag": jnp.asarray(tag)}, jnp.asarray(valid)),
            lambda r: (r["f"], r["tag"]), ascending)
        got_v = np.asarray(out.valid)
        got_f, got_t = (np.asarray(out.data[k])[got_v] for k in ("f", "tag"))
        sign = 1 if ascending else -1
        vf, vt = f[valid], tag[valid]
        order = np.lexsort((sign * vt, sign * vf))  # NaN last, as np.sort
        check(f"float_nan_tuple_sort_8shards_{word}",
              np.array_equal(got_f, vf[order], equal_nan=True)
              and np.array_equal(got_t, vt[order]))

    kv = w.parallelize(vals).map(lambda x: {"key": x % 13, "value": jnp.int32(1)})
    counts = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
              for r in kv.reduce_by_key(lambda a, b: a + b, 0).collect()}
    exp = {}
    for v in vals:
        exp[int(v) % 13] = exp.get(int(v) % 13, 0) + 1
    check("reduce_by_key_hash_exchange", counts == exp)

    l = w.parallelize(np.arange(64, dtype=np.int32)).map(
        lambda x: {"key": x % 8, "value": x})
    r = w.parallelize(np.arange(32, dtype=np.int32)).map(
        lambda x: {"key": x % 8, "value": x * 2})
    rows = l.join(r).collect()
    got_j = sorted((int(np.asarray(x["key"])), int(np.asarray(x["value"][0])),
                    int(np.asarray(x["value"][1]))) for x in rows)
    exp_j = sorted((a % 8, a, b * 2) for a in range(64) for b in range(32)
                   if a % 8 == b % 8)
    check("distributed_join", got_j == exp_j)

    # ---- adaptive shuffle engine: overflow retry + capacity memory --------
    # (DESIGN.md §6) a deliberately tiny capacity factor forces every
    # exchange bucket to overflow; results must still match the oracle and
    # the capacity memory must remove the retry on the second run.
    wt = IWorker(
        ICluster(IProperties({"ignis.executor.instances": "8",
                              "ignis.shuffle.capacity.factor": "0.05"})),
        "python")
    vals_t = rng.integers(0, 1000, 1024).astype(np.int32)
    frame = wt.parallelize(vals_t).sort()
    got_t = [int(x) for x in frame.collect()]
    check("overflow_sort_correct", got_t == sorted(int(v) for v in vals_t))
    st1 = wt.shuffle_stats()
    check("overflow_sort_retried", st1["overflow_retries"] >= 1)
    got_t2 = [int(x) for x in frame.collect()]
    st2 = wt.shuffle_stats()
    check("overflow_sort_stable", got_t2 == got_t)
    check("capacity_memory_no_second_retry",
          st2["overflow_retries"] == st1["overflow_retries"]
          and st2["wide_plan_misses"] == st1["wide_plan_misses"]
          and st2["capacity_memory_hits"] > st1["capacity_memory_hits"])

    # a tuple-key sort whose buckets overflow: the retry keeps every row
    retries = wt.shuffle_stats()["overflow_retries"]
    check("overflow_tuple_sort_correct", tuple_sort_ok(wt, "overflow", 3, True, 8 * 64))
    check("overflow_tuple_sort_retried", wt.shuffle_stats()["overflow_retries"] > retries)
    st2 = wt.shuffle_stats()

    # hash-exchange overflow (partitionBy with 5-key skew at p=8, C≈1)
    pb = wt.parallelize(vals_t).map(
        lambda x: {"key": x % 5, "value": x}).partition_by()
    vals_back = sorted(int(np.asarray(r["value"])) for r in pb.collect())
    check("overflow_hash_rows_preserved",
          vals_back == sorted(int(v) for v in vals_t))
    st3 = wt.shuffle_stats()
    check("overflow_hash_retried", st3["overflow_retries"] > st2["overflow_retries"])

    # join under tiny capacity: exchange retry, then fan-out retry, oracle match
    lt = wt.parallelize(np.arange(256, dtype=np.int32)).map(
        lambda x: {"key": x % 4, "value": x})
    rt = wt.parallelize(np.arange(64, dtype=np.int32)).map(
        lambda x: {"key": x % 4, "value": x * 2})
    got_tj = sorted((int(np.asarray(x["key"])), int(np.asarray(x["value"][0])),
                     int(np.asarray(x["value"][1])))
                    for x in lt.join(rt, max_matches=2).collect())
    exp_tj = sorted((a % 4, a, b * 2) for a in range(256) for b in range(64)
                    if a % 4 == b % 4)
    check("overflow_join_correct", got_tj == exp_tj)
    check("overflow_join_fanout_retried", wt.shuffle_stats()["fanout_retries"] >= 1)
    check("bytes_moved_recorded", wt.shuffle_stats()["bytes_moved"] > 0)

    # ---- comm layer (MPI analogue) -----------------------------------------
    ctx = w.context
    x = comm.shard_rows(ctx, jnp.arange(16, dtype=jnp.float32))
    check("allreduce", float(comm.allreduce(ctx, x)) == float(np.arange(16).sum()))
    g = comm.gather(ctx, x)
    check("allgather", np.array_equal(np.asarray(g), np.arange(16, dtype=np.float32)))
    y = comm.ppermute(ctx, x, shift=1)
    check("ppermute_ring", np.array_equal(
        np.asarray(y).reshape(8, 2), np.roll(np.arange(16).reshape(8, 2), 1, axis=0)))
    a2a = comm.alltoall(ctx, comm.shard_rows(ctx, jnp.arange(64, dtype=jnp.int32)))
    check("alltoall_shape", np.asarray(a2a).shape == (64,))
    try:
        comm.alltoall(ctx, comm.shard_rows(ctx, jnp.arange(24, dtype=jnp.int32)))
        check("alltoall_indivisible_raises", False)
    except ValueError:
        check("alltoall_indivisible_raises", True)

    # ---- nonblocking + persistent conformance at p=8 ------------------------
    # (the p=1 matrix is tests/test_collectives.py; here the wire patterns
    # are real 8-way exchanges, checked against the same NumPy oracles)
    a2a_i = comm.ialltoall(
        ctx, comm.shard_rows(ctx, jnp.arange(64, dtype=jnp.int32))).wait()
    check("ialltoall_transpose_8way", np.array_equal(
        np.asarray(a2a_i), np.arange(64).reshape(8, 8).T.reshape(-1)))
    xi = comm.shard_rows(ctx, jnp.arange(16, dtype=jnp.int32) - 16)
    check("iallreduce_max_all_negative_int",
          int(comm.iallreduce(ctx, xi, op="max").wait()) == -1)
    ex = comm.exscan(ctx, comm.shard_rows(ctx, jnp.ones(8, jnp.int32)))
    check("exscan_rank_prefix", np.array_equal(np.asarray(ex), np.arange(8)))
    plan8 = comm.persistent(ctx, "allreduce", x)
    s0 = comm.comm_stats()
    # a HELD plan skips the cache entirely (init-once/invoke-many): no
    # misses, and no lookups either
    reps = [float(plan8(x)) for _ in range(3)]
    s1 = comm.comm_stats()
    check("persistent_invoke_many_stable",
          reps == [float(np.arange(16).sum())] * 3)
    # re-RESOLVING the same (coll, mesh, aval) key must be pure cache hits
    for _ in range(2):
        comm.persistent(ctx, "allreduce", x)
    s2 = comm.comm_stats()
    check("persistent_plan_cache_hit_8way",
          s2["coll_plan_misses"] == s0["coll_plan_misses"]
          and s2["coll_plan_hits"] >= s1["coll_plan_hits"] + 2)

    # ---- communicator groups (MPI_Comm_split over the mesh) ----------------
    g0, g1 = ctx.split(2)
    check("split_sizes", g0.executors == 4 and g1.executors == 4)
    check("split_disjoint_devices",
          not (set(g0.mesh.devices.flat) & set(g1.mesh.devices.flat)))
    # collectives inside a group must not leak across the boundary: each
    # group allreduces ITS residents only
    x0 = comm.shard_rows(g0, jnp.arange(8, dtype=jnp.float32))         # 0..7
    x1 = comm.shard_rows(g1, jnp.arange(8, 16, dtype=jnp.float32))     # 8..15
    check("group_allreduce_isolated",
          float(comm.allreduce(g0, x0)) == 28.0
          and float(comm.allreduce(g1, x1)) == 92.0)
    check("group_gather_local",
          np.array_equal(np.asarray(comm.gather(g1, x1)),
                         np.arange(8, 16, dtype=np.float32)))
    # world collectives are untouched by the existence of groups
    check("world_allreduce_after_split",
          float(comm.allreduce(ctx, comm.shard_rows(ctx, jnp.arange(16, dtype=jnp.float32))))
          == 120.0)
    # inter-group reshard edge: a group collective accepts blocks committed
    # to the OTHER group (device_put sub-mesh -> sub-mesh)
    check("intergroup_reshard_collective",
          float(comm.allreduce(g1, x0)) == 28.0)
    # nonblocking handles are group-portable and await out of ORDER: world
    # and both halves in flight together, drained newest-first
    h_w = comm.iallreduce(
        ctx, comm.shard_rows(ctx, jnp.arange(16, dtype=jnp.float32)))
    h_0 = comm.iallreduce(g0, x0)
    h_1 = comm.igather(g1, x1)
    check("out_of_order_group_awaits",
          np.array_equal(np.asarray(h_1.wait()),
                         np.arange(8, 16, dtype=np.float32))
          and float(h_0.wait()) == 28.0 and float(h_w.wait()) == 120.0)
    # nested split: a group is itself splittable
    n0, n1 = g0.split(2)
    check("nested_split", n0.executors == 2
          and float(comm.allreduce(n0, comm.shard_rows(n0, jnp.arange(4, dtype=jnp.float32)))) == 6.0)

    # ---- gang-scheduled concurrent jobs on disjoint groups -----------------
    from repro.core.job import IJob as _IJob

    wg = IWorker(w.cluster, "python")
    gg0, gg1 = wg.groups(2)
    vals_g = rng.integers(0, 10_000, 1024).astype(np.int32)
    jobA = _IJob("gangA", group=gg0)
    jobB = _IJob("gangB", group=gg1)
    fA = wg.parallelize(vals_g).sort().collect_async(job=jobA)
    kvg = wg.parallelize(vals_g).map(lambda x: {"key": x % 11, "value": jnp.int32(1)})
    fB = kvg.reduce_by_key(lambda a, b: a + b, 0).collect_async(job=jobB)
    check("gang_sort_on_group",
          [int(x) for x in fA.result(120)] == sorted(int(v) for v in vals_g))
    counts_g = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
                for r in fB.result(120)}
    exp_g = {}
    for v in vals_g:
        exp_g[int(v) % 11] = exp_g.get(int(v) % 11, 0) + 1
    check("gang_rbk_on_group", counts_g == exp_g)
    check("gang_jobs_tagged",
          jobA.stats()["groups"] == ["data[0:4]"]
          and jobB.stats()["groups"] == ["data[4:8]"])
    check("gang_group_reshards", wg.shuffle_stats()["group_reshards"] >= 2)
    check("gang_tasks_counted",
          jobA.scheduler.stats["gang_tasks"] >= 2)
    # a driver-thread use_group binding rides along into the submission
    with wg.use_group(gg0):
        fbind = wg.parallelize(vals_g).sort().collect_async()
    check("driver_binding_propagates",
          fbind.task.group is gg0
          and [int(x) for x in fbind.result(120)] == sorted(int(v) for v in vals_g))
    # native app on a subset of executors (paper Fig. 9): the bound context
    # inside the app IS the group communicator
    from repro.core.native import ignis_export

    wsg = IWorker(w.cluster, "spmd")
    h0, _h1 = wsg.groups(2)

    @ignis_export("mesh_probe")
    def mesh_probe(ctx_, data=None, valid=None):
        assert ctx_.executors == 4, ctx_.executors
        return data, valid

    probe = wsg.call("mesh_probe", wsg.parallelize(np.arange(32, dtype=np.int32)))
    got_probe = probe.collect_async(group=h0).result(120)
    check("native_on_subset", [int(x) for x in got_probe] == list(range(32)))

    # ---- native HPC apps at p=8 --------------------------------------------
    from repro.apps.stencil import cg_native, laplacian_matvec_ref

    b = np.random.default_rng(1).normal(size=256).astype(np.float32)
    xs = cg_native(ctx.mesh, ctx.axis, jnp.asarray(b), 400)
    res = float(jnp.abs(laplacian_matvec_ref(xs) - jnp.asarray(b)).max())
    check("cg_8way", res < 5e-2)

    # ---- job scheduler: hybrid native+dataflow job at p=8 ------------------
    from repro.core.job import IJob
    from repro.core.native import ignis_export
    from repro.apps.stencil import stencil_native

    ws8 = IWorker(w.cluster, "spmd")
    grid = np.random.default_rng(2).normal(size=(16, 8)).astype(np.float32)
    job = IJob("hybrid8")
    st_f = ws8.call(
        "stencil_app", ws8.parallelize(grid), iters=4
    ).collect_async(job=job)
    kv8 = w.parallelize(vals).map(lambda x: {"key": x % 7, "value": jnp.int32(1)})
    cnt_f = kv8.reduce_by_key(lambda a, b: a + b, 0).collect_async(job=job)
    got_st = np.stack([np.asarray(r) for r in st_f.result(120)])
    native8 = np.asarray(
        stencil_native(ws8.context.mesh, ws8.context.axis, jnp.asarray(grid), 4)
    )
    check("job_native_stage_p8", np.allclose(got_st, native8, atol=1e-6))
    counts8 = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
               for r in cnt_f.result(120)}
    exp8 = {}
    for v in vals:
        exp8[int(v) % 7] = exp8.get(int(v) % 7, 0) + 1
    check("job_hybrid_dataflow_p8", counts8 == exp8)
    st_job = job.stats()
    check("job_one_dag_p8",
          st_job["native"] >= 1 and st_job["actions"] == 2
          and st_job["failed"] == 0 and len(st_job["workers"]) == 2)

    # call_partitions at p=8: partition-preserving native + kill_block repair
    @ignis_export("scale8")
    def scale8(ctx, data=None, valid=None):
        return data * jnp.int32(int(ctx.var("k", 2))), valid

    dfp = w.parallelize(np.arange(64, dtype=np.int32), blocks=4)
    sc = w.call_partitions("scale8", dfp, k=3).persist()
    got_sc = sorted(int(x) for x in sc.collect())
    check("call_partitions_p8", got_sc == [x * 3 for x in range(64)])
    check("call_partitions_blocks_p8", len(sc.node.result) == 4)
    from repro.core.dag import DagEngine
    DagEngine.kill_block(sc.node, 1)
    check("call_partitions_repair_p8",
          sorted(int(x) for x in sc.collect()) == got_sc)

    # early-exit take at p=8: one block materialised out of four
    it0 = w.engine.stats["iter_block_computes"]
    tk = w.parallelize(np.arange(64, dtype=np.int32), blocks=4).map(
        lambda x: x + 1).take(3)
    check("take_early_exit_p8",
          [int(x) for x in tk] == [1, 2, 3]
          and w.engine.stats["iter_block_computes"] - it0 == 1)

    # ---- pipeline parallelism (4 stages × 8 microbatches) -------------------
    pmesh = make_pp_mesh(4, 1)
    S, M, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, d, d)) * 0.3
    xm = jax.random.normal(key, (M, mb, d))

    def stage_fn(wmat, x):
        return jnp.tanh(x @ wmat)

    with compat.set_mesh(pmesh):
        got_pp = pipeline_apply(ws, xm, stage_fn, pmesh)
    ref_pp = reference_apply(ws, xm, stage_fn)
    check("pipeline_1f1b", bool(jnp.allclose(got_pp, ref_pp, atol=1e-5)))

    # ---- elastic: save at dp=8, restore at dp=4 ----------------------------
    import tempfile

    from repro.checkpoint import save
    from repro.configs import get_config
    from repro.distributed.elastic import restore_elastic
    from repro.models import build_model

    cfg = get_config("olmo-1b").reduced()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(1))
    with tempfile.TemporaryDirectory() as td:
        mesh8 = make_local_mesh(8, 1)
        p8 = jax.device_put(params)  # pretend it lived on dp=8
        save(td, 1, {"params": p8})
        mesh4 = make_local_mesh(4, 2)
        out = restore_elastic(td, 1, cfg, mesh4, {"params": params})
        same = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out["params"]))
        )
        check("elastic_reshard_8to4x2", same)

    # ---- shard_map expert-parallel MoE == GSPMD reference ------------------
    from jax.sharding import NamedSharding, PartitionSpec as P2

    from repro.configs import get_config
    from repro.models.moe import make_moe_params, moe_ffn_bsd
    from repro.models.moe_ep import ep_applicable, moe_ffn_bsd_ep

    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced().with_overrides(
        num_experts=8, experts_per_token=2, d_model=32, d_ff=64, moe_ep=True,
        capacity_factor=8.0,  # no drops → exact parity
    )
    mesh2 = make_local_mesh(8, 1)
    pmoe = make_moe_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    xin = jax.random.normal(jax.random.PRNGKey(4), (16, 4, 32))
    with compat.set_mesh(mesh2):
        xs2 = jax.device_put(xin, NamedSharding(mesh2, P2("data")))
        ps2 = jax.device_put(pmoe, NamedSharding(mesh2, P2()))

        def fmoe(x, p):
            assert ep_applicable(cfg)
            return moe_ffn_bsd_ep(x, p, cfg)

        y_ep, _aux = jax.jit(fmoe)(xs2, ps2)
    y_ref, _ = moe_ffn_bsd(xin, pmoe, cfg)
    check("moe_ep_parity", float(jnp.abs(y_ep - y_ref).max()) < 1e-4)

    # ---- kernel tier at p=8 (docs/kernels.md): interpret vs off must be
    # bit-identical with identical retry trajectories, with the segment
    # kernel actually engaged on reduceByKey (partitionBy and join run the
    # same exchange on both tiers)
    res8, ctr8 = {}, {}
    for mode in ("interpret", "off"):
        wk = IWorker(ICluster(IProperties({
            "ignis.executor.instances": "8", "ignis.kernels": mode})),
            "python")
        kvk = wk.parallelize(vals).map(
            lambda x: {"key": x % 13, "value": jnp.int32(1)})
        rbk = sorted((int(np.asarray(r["key"])), int(np.asarray(r["value"])))
                     for r in kvk.reduce_by_key(lambda a, b: a + b, 0).collect())
        pbk = sorted(int(np.asarray(r["value"]))
                     for r in wk.parallelize(vals[:512]).map(
                         lambda x: {"key": x % 5, "value": x})
                     .partition_by().collect())
        lk = wk.parallelize(np.arange(64, dtype=np.int32)).map(
            lambda x: {"key": x % 8, "value": x})
        rk = wk.parallelize(np.arange(32, dtype=np.int32)).map(
            lambda x: {"key": x % 8, "value": x * 2})
        jk = sorted((int(np.asarray(x["key"])), int(np.asarray(x["value"][0])),
                     int(np.asarray(x["value"][1])))
                    for x in lk.join(rk).collect())
        res8[mode] = (rbk, pbk, jk)
        sk = wk.shuffle_stats()
        ctr8[mode] = (sk["overflow_retries"], sk["fanout_retries"])
        if mode == "interpret":
            check("p8_kernel_hits", sk["kernel_hits"] >= 1)
        else:
            check("p8_kernel_off_no_hits", sk["kernel_hits"] == 0)
    check("p8_kernel_on_off_equal", res8["interpret"] == res8["off"])
    check("p8_kernel_retry_counters_equal", ctr8["interpret"] == ctr8["off"])

    # ---- streaming multi-tenant front end on gang groups + serve front
    # door in ONE job DAG (docs/streaming.md): 4 tenant pumps on groups(4)
    # run concurrently with continuous-batching decode ticks, all through
    # the shared JobScheduler — the paper's hybrid pattern at serving time
    import threading

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.engine import ServeEngine
    from repro.streaming import (
        ServeFrontDoor, StreamContext, TenantFrontEnd, TenantRequestSource)

    ws = IWorker(ICluster(IProperties({
        "ignis.executor.instances": "8",
        "ignis.stream.batch.rows": "16"})), "python")
    fe = TenantFrontEnd(ws, n_groups=4)
    for i in range(4):
        fe.admit(f"t{i}", TenantRequestSource(i, seed=21, limit=160),
                 init_state=np.zeros((2,), np.int64))

    scfg = get_config("ignis-tiny")
    bundle = build_model(scfg)
    sparams = bundle.init(jax.random.PRNGKey(0))
    eng = ServeEngine(bundle, sparams, slots=2, cache_len=64)
    fd = ServeFrontDoor(eng, ws, group=fe.groups[0], job=fe.job,
                        telemetry=fe.telemetry)
    rng_s = np.random.default_rng(7)
    prompts = [rng_s.integers(0, scfg.vocab_size, 5, dtype=np.int32)
               for _ in range(6)]
    tix = [fd.submit(p, max_new_tokens=4, tenant="serve") for p in prompts]

    serve_n = {}
    th = threading.Thread(
        target=lambda: serve_n.update(n=len(fd.run_until_drained())),
        daemon=True)
    th.start()
    res_s = fe.run()
    th.join(300)
    check("p8_stream_serve_overlap_drained",
          not th.is_alive() and serve_n.get("n") == 6)

    ok_iso = True
    for i in range(4):
        solo = StreamContext(
            ws, TenantRequestSource(i, seed=21, limit=160),
            tenant=f"solo{i}", init_state=np.zeros((2,), np.int64)).run()
        ok_iso = ok_iso and bool((res_s[f"t{i}"] == solo).all())
    check("p8_stream_tenants_match_solo_oracles", ok_iso)

    # decode output is unchanged by the multi-tenant load: every ticket
    # matches the single-request greedy reference
    def greedy_ref(prompt, n_new):
        toks = jnp.asarray(prompt, jnp.int32)[None]
        logits, cache = bundle.prefill(sparams, tokens=toks,
                                       cache_len=len(prompt) + n_new + 1)
        out = [int(jnp.argmax(logits[0]))]
        for _ in range(n_new - 1):
            t = jnp.asarray([[out[-1]]], jnp.int32)
            logits, cache = bundle.decode_step(sparams, cache, t)
            out.append(int(jnp.argmax(logits[0])))
        return out

    check("p8_serve_greedy_parity_under_load",
          all(t.result(10.0).tokens == greedy_ref(p, 4)
              for t, p in zip(tix, prompts)))

    # one DAG: tick tasks and all 40 micro-batches are gang-pinned job
    # tasks; the shared telemetry splits per tenant
    js = fe.job.stats()
    check("p8_stream_serve_one_dag",
          js["serve"] >= 1 and js["gang"] == js["tasks"]
          and len(js["groups"]) == 4)
    check("p8_stream_telemetry_per_tenant",
          js["stream"]["tenants"]["serve"]["completed"] == 6
          and js["stream"]["completed"] == 46
          and js["stream"]["inflight"] == 0)

    # ---- elastic mesh: grow/shrink under cached partitions ----------------
    # compact cross-check of the dedicated tier (tests/_elastic_main.py,
    # DESIGN.md §14): a cached frame survives shrink(2)+grow(2) bit-identically
    # with zero lineage recomputes — resharding is pure data movement
    we = IWorker(ICluster(IProperties({"ignis.executor.instances": "8"})),
                 "python")
    dfe = we.parallelize(np.arange(4096, dtype=np.int32)).map(
        lambda x: x * 3 + 1).persist()
    oracle_e = [int(x) for x in dfe.collect()]
    we.shrink(2)
    mid = [int(x) for x in dfe.collect()]
    we.grow(2)
    es = we.metrics("elastic")
    check("p8_elastic_resize_bit_identical",
          mid == oracle_e and [int(x) for x in dfe.collect()] == oracle_e)
    check("p8_elastic_zero_recomputes",
          es["reshard_recomputes"] == 0 and es["reshard_moves"] > 0
          and es["grows"] == 1 and es["shrinks"] == 1
          and es["world_size"] == 8 and dfe.node.compute_count == 1)

    print("ALL_DISTRIBUTED_OK")


if __name__ == "__main__":
    main()
