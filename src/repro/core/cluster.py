"""Ignis / ICluster / IWorker — the job hierarchy (paper §3.2, Fig. 2).

A *Cluster* owns a device mesh (its "containers"); *Workers* are
programming-model execution contexts on that mesh — the multi-language
adaptation (DESIGN.md §2): instead of a Python worker and a C++ worker, a
job creates dataflow workers and SPMD workers that interoperate through
``importData`` (the inter-worker communicator: a resharding device_put on
the same fabric, zero host round-trips) — or, in "spark" mode, through the
serialize→host→deserialize pipe the paper benchmarks against.
"""
from __future__ import annotations

import os
import pickle
import threading
from pathlib import Path
from typing import Optional

import jax
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from repro.core import comm as comm_mod
from repro.core import compat
from repro.core import faults
from repro.core.context import IContext
from repro.core.dag import DagEngine, TaskNode, node_sig
from repro.core.metrics import Counters, MetricsTree, warn_deprecated
from repro.core.shuffle_plan import ShuffleManager
from repro.core.dataframe import IDataFrame
from repro.core.native import get_app, load_library
from repro.core.partition import Block, block_aval, concat_blocks, from_host, place_block
from repro.core.properties import IProperties
from repro.core.textlambda import ISource
from repro.kernels.registry import DEFAULT_BLOCKS, KernelRegistry
from repro.profile.spans import span


#: persistent compile cache used when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path in the checkout (the path is part of the cache key)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


class Ignis:
    """Framework lifecycle (paper Fig. 6 lines 6/42)."""

    _started = False

    @classmethod
    def start(cls):
        """Start the framework. Compiled programs persist across processes:
        in ``JAX_COMPILATION_CACHE_DIR`` where it is set (jax reads it
        itself), else in ``COMPILE_CACHE_DIR``."""
        if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
            path = str(COMPILE_CACHE_DIR)
            if jax.config.jax_compilation_cache_dir != path:
                jax.config.update("jax_compilation_cache_dir", path)
                # re-open the cache at the new path if a compile opened it
                compilation_cache.reset_cache()
        cls._started = True

    @classmethod
    def stop(cls):
        cls._started = False

    @classmethod
    def running(cls) -> bool:
        return cls._started

    @classmethod
    def scheduler(cls):
        """The process-wide job scheduler (docs/driver.md)."""
        from repro.core.job import default_scheduler

        return default_scheduler()

    @classmethod
    def job(cls, name: str = "job"):
        """Open a named job: a group of async submissions scheduled as one
        cross-worker DAG (paper §3.2 job hierarchy; docs/driver.md)."""
        from repro.core.job import IJob

        return IJob(name)


class ICluster:
    """A group of executor containers = a device mesh slice (paper §3.2)."""

    def __init__(self, props: Optional[IProperties] = None, mesh=None):
        self.props = props or IProperties()
        if mesh is None:
            n = min(
                self.props.get_int("ignis.executor.instances", 1), len(jax.devices())
            )
            mesh = compat.make_mesh((max(n, 1),), ("data",))
        self.mesh = mesh
        self.workers: list[IWorker] = []

    # paper §4: remote commands to containers — host-side here
    def execute(self, fn, *args, **kw):
        return fn(*args, **kw)

    def execute_script(self, src: str):
        scope = {}
        exec(src, scope)  # noqa: S102
        return scope

    def send_file(self, src: str, dst: str):
        with open(src, "rb") as f, open(dst, "wb") as g:
            g.write(f.read())

    sendFile = send_file
    executeScript = execute_script


class IWorker:
    """One programming-model context bound to a cluster (paper §3.2).

    kind: "dataflow" (IDataFrame ops) | "spmd" (native collective apps).
    Both share the cluster mesh — that is the paper's whole point.
    """

    def __init__(self, cluster: ICluster, kind: str = "dataflow", name: str = ""):
        if kind in ("python", "cpp", "java"):  # paper-style language names
            kind = "dataflow"
        self.cluster = cluster
        self.kind = kind
        self.name = name or f"{kind}-{len(cluster.workers)}"
        self._base_context = IContext(cluster.mesh, "data", cluster.props, self)
        self._ctx_local = threading.local()
        self.engine = DagEngine(
            fusion=cluster.props.get_bool("ignis.fusion.enabled", True),
            plan_cache_size=cluster.props.get_int("ignis.fusion.plan.cache.size", 128),
            fusion_mode=cluster.props.get("ignis.fusion.mode", "static"),
        )
        # the cost model (docs/profiling.md): every worker carries one —
        # cost-mode fusion prices chains through it, the scheduler feeds it
        # task-duration history, and timeout=auto reads that history. Pure
        # python and cheap; imported lazily to keep core importable alone.
        from repro.profile.cost import CostModel

        self.engine.cost_model = CostModel()
        self.mode = cluster.props.get("ignis.mode", "ignis")
        self.capacity_factor = cluster.props.get_float("ignis.shuffle.capacity.factor", 2.0)
        self.join_max_matches = cluster.props.get_int("ignis.join.max.matches", 8)
        self.shuffle = ShuffleManager(
            self._base_context,
            worker=self,
            capacity_factor=self.capacity_factor,
            join_max_matches=self.join_max_matches,
            plan_cache_size=cluster.props.get_int("ignis.shuffle.plan.cache.size", 64),
            headroom=cluster.props.get_float("ignis.shuffle.memory.headroom", 1.25),
            kernels=KernelRegistry(
                mode=cluster.props.get("ignis.kernels", "auto"),
                blocks=cluster.props.get("ignis.kernels.blocks", DEFAULT_BLOCKS),
                tune_cache_size=cluster.props.get_int(
                    "ignis.kernels.tune.cache.size", 512),
            ),
        )
        self._libraries: list[str] = []
        # unified introspection tree (docs/profiling.md): every subsystem's
        # counter namespace mounted under one surface. `coll` is a thunk —
        # the collective engine is process-wide and snapshots under its own
        # lock. JobTracer.attach(worker=...) mounts `profile` here.
        # elastic mesh telemetry (docs/elasticity.md): resize events and the
        # incremental-reshard counter split — `reshard_moves` (blocks whose
        # ownership changed, moved as pure data) vs `reshard_unchanged`
        # (cached blocks a resize left in place) vs `reshard_recomputes`
        # (blocks LOST mid-move — elastic.reshard faults — handed back to
        # block-wise lineage repair; 0 on every clean resize)
        self.elastic_stats = Counters("elastic", {
            "grows": 0,
            "shrinks": 0,
            "world_size": self._base_context.executors,
            "reshard_moves": 0,
            "reshard_unchanged": 0,
            "reshard_recomputes": 0,
        })
        self._metrics = MetricsTree(
            stages=self.engine.stats,
            shuffle=self.shuffle.stats,
            kernels=self.shuffle.kernels.stats,
            coll=comm_mod.comm_stats,
            elastic=self.elastic_stats,
        )
        # job-scheduler serialisation points (core/job.py): the base lock
        # covers the whole worker; gang-scheduled tasks instead hold one
        # GROUP lock each, so two tasks on disjoint sub-meshes of this
        # worker run concurrently. All re-entrant so nested eager actions
        # inside a running native task execute inline.
        self._job_lock = threading.RLock()
        # id(ctx) → (ctx, lock, pinned): the ctx reference pins the id
        # against reuse; pinned entries (worker.groups() splits) live
        # forever, ad-hoc entries are evicted FIFO beyond the cap so a
        # driver minting a fresh group per job cannot grow this unboundedly
        from collections import OrderedDict

        self._group_locks: "OrderedDict[int, tuple]" = OrderedDict()
        # n_groups → (base context the split was built from, groups): the
        # base reference is the world-identity the cache revalidates against
        # — a grow/shrink swaps _base_context, so stale sub-mesh splits are
        # rebuilt on next use instead of surviving the resize
        self._groups: dict[int, tuple] = {}
        self._groups_guard = threading.Lock()
        # serialises grow/shrink against each other (drain handles jobs)
        self._resize_lock = threading.RLock()
        # fault tolerance (docs/fault_tolerance.md): executors reported lost
        # (containers the resource manager reclaimed) and the registry of
        # cached nodes whose blocks a lost executor takes with it. WeakSet:
        # dropping every frame reference releases the lineage as before.
        import weakref

        self.executor_blacklist: set[int] = set()
        self._cached_nodes = weakref.WeakSet()
        cluster.workers.append(self)

    _GROUP_LOCK_CAP = 256

    # ------------------------------------------------------------------
    # communicator groups (MPI_Comm_split over the worker mesh)
    # ------------------------------------------------------------------
    @property
    def context(self) -> IContext:
        """The worker's ACTIVE communicator: the base (world) context, or
        the group communicator installed by ``use_group`` on this thread —
        how a gang-scheduled task retargets every collective, wide stage
        and native app onto its sub-mesh (docs/collectives.md)."""
        return getattr(self._ctx_local, "ctx", None) or self._base_context

    def use_group(self, ctx: "IContext | None"):
        """Context manager binding this THREAD's active communicator."""
        import contextlib

        @contextlib.contextmanager
        def _bind():
            prev = getattr(self._ctx_local, "ctx", None)
            self._ctx_local.ctx = ctx
            try:
                yield ctx or self._base_context
            finally:
                self._ctx_local.ctx = prev

        return _bind()

    def groups(self, n_groups: int) -> "list[IContext]":
        """The worker's cached ``n_groups``-way split of its base mesh.
        Cached so every job gang-scheduled at the same width shares one set
        of group communicators — and one group lock per slice, keeping two
        GROUPED jobs from oversubscribing the same slice concurrently.
        Ungrouped (world) tasks hold the worker lock, which deliberately
        does not exclude group locks: for strict slice isolation keep a
        worker's concurrent jobs all-grouped (mixing is safe — results are
        correct and caches are locked — just oversubscribed;
        docs/collectives.md)."""
        with self._groups_guard:
            entry = self._groups.get(n_groups)
            # revalidate against the CURRENT world, not just the blacklist:
            # a grow/shrink swaps _base_context, and a split built over the
            # old world would otherwise keep handing out stale sub-meshes
            # (docs/elasticity.md; the pre-elastic bug kept them forever)
            if entry is not None and entry[0] is not self._base_context:
                for g in entry[1]:
                    self._group_locks.pop(id(g), None)
                entry = None
            if entry is None:
                gs = self._base_context.split(n_groups)
                entry = self._groups[n_groups] = (self._base_context, gs)
                for g in gs:
                    self._group_locks[id(g)] = (g, threading.RLock(), True)
            gs = entry[1]
            # the cache must not bypass the executor blacklist: a split built
            # before a kill_executor would otherwise keep handing out groups
            # over the lost rank while a fresh split raises. The cache itself
            # survives — restore_executor() re-admits the same group objects.
            lost = sorted({r for g in gs for r in g.group_ranks
                           if r in self.executor_blacklist})
            if lost:
                raise ValueError(
                    f"groups({n_groups}) spans blacklisted executors {lost} "
                    f"(lost containers); restore_executor() to re-admit them")
            return gs

    def group_lock(self, ctx: IContext) -> threading.RLock:
        """The job lock guarding a group communicator's device slice. An
        unknown (caller-built) group context gets its own lock on demand;
        such ad-hoc entries are evicted FIFO beyond ``_GROUP_LOCK_CAP``
        (tasks created earlier keep their lock object — at worst an
        evicted-and-reminted slice is briefly oversubscribed, never
        corrupted, since every task still binds its own communicator)."""
        with self._groups_guard:
            entry = self._group_locks.get(id(ctx))
            if entry is None:
                entry = self._group_locks[id(ctx)] = (ctx, threading.RLock(), False)
                if len(self._group_locks) > self._GROUP_LOCK_CAP:
                    for key, (_c, _l, pinned) in list(self._group_locks.items()):
                        if not pinned:
                            del self._group_locks[key]
                            break
            return entry[1]

    # ------------------------------------------------------------------
    # elastic mesh: runtime grow/shrink (docs/elasticity.md, DESIGN.md §14)
    # ------------------------------------------------------------------
    def _world_devices(self) -> list:
        devs = np.asarray(self._base_context.mesh.devices)
        if devs.ndim != 1:
            raise ValueError(
                "elastic resize supports 1-D data meshes only "
                f"(this worker's mesh has axes {self._base_context.mesh.axis_names})")
        return list(devs.flat)

    def grow(self, n: int = 1) -> int:
        """Admit ``n`` executor ranks at runtime: in-flight tasks drain on
        the old communicator, the base context rebinds a mesh extended with
        ``n`` free devices, and cached partitions reshard incrementally
        (docs/elasticity.md). Returns the new world size."""
        if n < 1:
            raise ValueError(f"grow() needs n >= 1, got {n}")
        with self._resize_lock:
            cur = self._world_devices()
            have = {d.id for d in cur}
            pool = [d for d in jax.devices() if d.id not in have]
            if len(pool) < n:
                raise ValueError(
                    f"grow({n}): only {len(pool)} free device(s) beyond the "
                    f"current {len(cur)}-executor world")
            return self._resize(cur + pool[:n])

    def shrink(self, ranks) -> int:
        """Retire executor ranks at runtime: ``shrink(2)`` retires the two
        highest ranks, ``shrink([1, 3])`` retires exactly those ranks. At
        least one rank must survive. Cached blocks owned by retired devices
        move onto the survivors (incremental reshard — pure data movement,
        no lineage recompute). Returns the new world size."""
        with self._resize_lock:
            cur = self._world_devices()
            if isinstance(ranks, int):
                if ranks < 1:
                    raise ValueError(f"shrink() needs >= 1 rank, got {ranks}")
                ranks = range(len(cur) - ranks, len(cur))
            retire = sorted({int(r) for r in ranks})
            if not retire:
                raise ValueError("shrink() needs at least one rank")
            bad = [r for r in retire if not 0 <= r < len(cur)]
            if bad:
                raise ValueError(
                    f"shrink() ranks {bad} out of range for {len(cur)} executors")
            if len(retire) >= len(cur):
                raise ValueError(
                    f"shrink({retire}) would retire the whole {len(cur)}-rank "
                    f"world; at least one executor must survive")
            gone = set(retire)
            return self._resize([d for i, d in enumerate(cur) if i not in gone])

    def _resize(self, new_devices: list) -> int:
        """Swap the base communicator onto ``new_devices`` under a full
        drain: the worker job lock plus every pinned group lock (the
        ``groups()`` splits gang tasks serialise on) are held, so in-flight
        tasks finish on the OLD communicator and later submissions bind the
        resized mesh via ``worker.context``. Ad-hoc caller-built groups are
        not drained — the same tolerated oversubscription as group-lock
        eviction (DESIGN.md §8); their tasks keep computing on their own
        (stale but intact) sub-meshes. Call from a driver thread that holds
        no job locks."""
        old = self._base_context
        with self._groups_guard:
            drain = [lock for (_c, lock, pinned) in self._group_locks.values()
                     if pinned]
        held = []
        self._job_lock.acquire()
        held.append(self._job_lock)
        for lk in drain:
            lk.acquire()
            held.append(lk)
        try:
            old_devs = self._world_devices()
            old_world = frozenset(old_devs)
            new_ctx = IContext(
                compat.make_mesh_of(np.asarray(new_devices),
                                    old.mesh.axis_names),
                old.axis, self.cluster.props, self)
            new_ctx._vars = dict(old._vars)
            self._base_context = new_ctx
            # the blacklist is rank-indexed: re-key it by device identity
            # (a blacklisted rank whose device was retired is simply gone)
            dev_rank = {d: i for i, d in enumerate(new_devices)}
            self.executor_blacklist = {
                dev_rank[old_devs[r]] for r in self.executor_blacklist
                if r < len(old_devs) and old_devs[r] in dev_rank}
            # cached splits of the old world are stale; groups() also
            # revalidates by base identity, this just frees the locks now
            with self._groups_guard:
                for _base, gs in self._groups.values():
                    for g in gs:
                        self._group_locks.pop(id(g), None)
                self._groups.clear()
            from repro.distributed.elastic import reshard_cached

            moves, kept, recomputes = reshard_cached(self, old_world, new_ctx)
            st = self.elastic_stats
            st["grows" if len(new_devices) > len(old_devs) else "shrinks"] += 1
            st["world_size"] = len(new_devices)
            st["reshard_moves"] += moves
            st["reshard_unchanged"] += kept
            st["reshard_recomputes"] += recomputes
            return len(new_devices)
        finally:
            for lk in reversed(held):
                lk.release()

    # ------------------------------------------------------------------
    # executor failure (paper §3.5: container loss + blacklist)
    # ------------------------------------------------------------------
    def _register_cached(self, node: TaskNode):
        """Track a node holding materialised blocks (persist / parallelize /
        checkpoint) so a simulated executor loss can take its shard."""
        self._cached_nodes.add(node)

    def kill_executor(self, rank: int, blacklist: bool = True) -> int:
        """Simulate losing the container of executor ``rank``: every cached
        node of this worker loses its ``rank``-th block (the paper's
        partition-per-executor model — repair recomputes them from lineage
        or restores them from a checkpoint on the next action), and the
        rank is blacklisted so new communicator groups avoid it until
        ``restore_executor``. Returns the number of blocks lost."""
        killed = 0
        for node in list(self._cached_nodes):
            if (node.result is not None and rank < len(node.result)
                    and node.result[rank] is not None):
                DagEngine.kill_block(node, rank)
                killed += 1
        if blacklist:
            self.executor_blacklist.add(int(rank))
        return killed

    def restore_executor(self, rank: int):
        """Lift the blacklist for a recovered/replaced executor."""
        self.executor_blacklist.discard(int(rank))

    # ------------------------------------------------------------------
    # introspection: stage compilation (DESIGN.md §5)
    # ------------------------------------------------------------------
    def explain(self, df: IDataFrame) -> str:
        """Physical plan of a frame's lineage — fused stages + boundaries,
        shuffle capacity annotations, shuffle telemetry."""
        return df.explain()

    def metrics(self, path: str | None = None) -> dict:
        """The worker's namespaced metrics tree (docs/profiling.md §metrics):
        ``stages/`` (DagEngine), ``shuffle/`` (ShuffleManager), ``kernels/``
        (kernel tier), ``coll/`` (process-wide collective engine), and
        ``profile/`` once a tracer is mounted. ``path`` selects one subtree
        (``metrics("stages")``); unknown paths raise ``KeyError``."""
        return self._metrics.snapshot(path)

    def mount_metrics(self, name: str, source) -> None:
        """Mount (or re-mount) a counter namespace on this worker's metrics
        tree — how JobTracer exposes ``profile/`` (docs/profiling.md)."""
        self._metrics.mount(name, source)

    def stage_stats(self) -> dict:
        """Deprecated facade over ``metrics("stages")`` — engine telemetry
        snapshot: node/block computes, fused stage runs, plan-cache
        hits/misses/evictions. Same keys as always."""
        warn_deprecated("IWorker.stage_stats()", 'IWorker.metrics("stages")')
        return self._metrics.snapshot("stages")

    def shuffle_stats(self) -> dict:
        """Deprecated facade over the ``shuffle`` + ``kernels`` + ``coll``
        metrics subtrees, merged flat exactly as before PR 9: adaptive
        shuffle engine telemetry (DESIGN.md §6) — exchanges, overflow/
        fan-out retries, deferred checks, capacity-memory hits, wide-plan
        compiles/hits, bytes moved — plus the kernel tier's selection/
        autotune counters (docs/kernels.md) and the collective engine's
        persistent-plan and handle counters (DESIGN.md §10; process-wide,
        so two workers sharing one mesh see one set of plan counters)."""
        warn_deprecated("IWorker.shuffle_stats()",
                        'IWorker.metrics("shuffle"/"kernels"/"coll")')
        return {**self._metrics.snapshot("shuffle"),
                **self._metrics.snapshot("kernels"),
                **self._metrics.snapshot("coll")}

    # ------------------------------------------------------------------
    # data ingestion (driver communicator)
    # ------------------------------------------------------------------
    @property
    def executors(self) -> int:
        return self.context.executors

    def _put(self, x):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(self.context.mesh, P(self.context.axis)))

    def parallelize(self, rows, blocks: int = 1) -> IDataFrame:
        p = self.executors
        if blocks <= 1:
            blk = [from_host(rows, p, put=self._put)]
        else:
            per = (len(rows) + blocks - 1) // blocks
            blk = [
                from_host(rows[i * per : (i + 1) * per], p, put=self._put)
                for i in range(blocks)
                if len(rows[i * per : (i + 1) * per])
            ]
        node = TaskNode("parallelize", [], fn=lambda _: blk, narrow=False)
        node.result = blk
        node.cached = True
        self._register_cached(node)
        # structural source signature: re-parallelizing same-shaped data maps
        # to the same lineage signature (shuffle capacity memory, DESIGN.md §6)
        node.sig = ("src", tuple(block_aval(b) for b in blk))
        return IDataFrame(self, node)

    def text_file(self, path: str, as_tokens: bool = False, blocks: int = 1):
        """Read a text file. Rows are (line-hash, length) pairs unless
        ``as_tokens`` — then the host tokenizer (the 'modality frontend' of
        text) maps words to ids and rows are token ids."""
        with open(path) as f:
            lines = [l.rstrip("\n") for l in f]
        if as_tokens:
            vocab: dict[str, int] = {}
            toks = []
            for line in lines:
                for w in line.split():
                    toks.append(vocab.setdefault(w, len(vocab)))
            self._text_vocab = vocab
            return self.parallelize(np.asarray(toks, np.int32), blocks)
        self._text_lines = lines
        rows = np.asarray([[hash(l) & 0x7FFFFFFF, len(l)] for l in lines], np.int32)
        return self.parallelize(rows, blocks)

    textFile = text_file

    def partition_json_file(self, path: str) -> IDataFrame:
        import json

        with open(path) as f:
            data = json.load(f)
        return self.parallelize(np.asarray(data))

    partitionJsonFile = partition_json_file

    # ------------------------------------------------------------------
    # inter-worker communicator (paper Fig. 4: importData)
    # ------------------------------------------------------------------
    def import_data(self, df: IDataFrame) -> IDataFrame:
        src_worker = df.worker

        def fn(parent_results):
            faults.check("reshard", kind="importData", src=src_worker.name,
                         dst=self.name)
            with span("import:" + df.node.op, src=src_worker.name, dst=self.name,
                      blocks=len(parent_results[0])):
                return reshard(parent_results[0])

        def reshard(blocks):
            out = []
            for b in blocks:
                if self.mode == "spark" or src_worker.mode == "spark":
                    # the paper's pipe: serialize → host → deserialize
                    data = pickle.loads(pickle.dumps(jax.device_get(b.data)))
                    valid = np.asarray(jax.device_get(b.valid))
                    out.append(
                        Block(
                            jax.tree.map(self._put, data),
                            self._put(valid),
                        )
                    )
                else:
                    # on-fabric reshard: MPI inter-worker communicator
                    out.append(
                        Block(jax.tree.map(self._put, b.data), self._put(b.valid))
                    )
            return out

        node = TaskNode("importData", [df.node], fn=fn, narrow=False)
        return IDataFrame(self, node)

    importData = import_data

    # ------------------------------------------------------------------
    # native SPMD apps (paper §5)
    # ------------------------------------------------------------------
    def load_library(self, path_or_module: str) -> list[str]:
        names = load_library(path_or_module)
        self._libraries.extend(names)
        return names

    loadLibrary = load_library

    def _resolve_app(self, fn_name, params):
        """Resolve (app callable, display name, merged params, sig token)
        from a registry name, a callable, or an ISource with addParams."""
        if isinstance(fn_name, ISource):
            src, params = fn_name.fn, {**fn_name.params, **params}
        else:
            src = fn_name
        app = get_app(src) if isinstance(src, str) else src
        name = src if isinstance(src, str) else getattr(src, "__name__", "app")
        isrc = ISource(src)
        isrc.params = dict(params)
        return app, name, params, isrc.token()

    @staticmethod
    def _native_args(ctx, parent_results):
        """Materialise a native app's data args on the app's communicator.
        Under gang scheduling the bound ctx is a group sub-mesh while parent
        blocks may live on the world mesh (or another group) — the
        device_put here is the inter-group reshard edge for native tasks."""
        if not parent_results:
            return ()
        faults.check("reshard", kind="native")
        b = place_block(concat_blocks(parent_results[0]), ctx.mesh, ctx.axis)
        return (b.data, b.valid)

    def void_call_async(self, fn_name, df: IDataFrame | None = None, job=None,
                        **params):
        """Async voidCall: the app runs as a native TaskNode inside the job
        DAG — it appears in job explain()/stats, executes under the worker's
        job lock, and gets the same scheduling/fault path as ``call`` instead
        of firing eagerly outside the graph. Returns an IFuture resolving to
        the app's return value.

        ``job`` is reserved for the IJob here; an app parameter literally
        named "job" must go through ``ISource.add_param`` (the eager
        ``void_call`` keeps the unrestricted param namespace)."""
        return self._void_call_task(fn_name, df, params, job)

    def _void_call_task(self, fn_name, df, params: dict, job):
        app, name, params, tok = self._resolve_app(fn_name, params)
        parents = [df.node] if df is not None else []
        worker = self
        out_cell: dict = {}

        def fn(parent_results):
            ctx = worker.context.bind(params)  # execution-time binding
            args = worker._native_args(ctx, parent_results)
            with span("native:" + name, call="voidCall"):
                out_cell["value"] = app(ctx, *args)
            return []  # void: no blocks enter the lineage

        node = TaskNode(f"voidCall:{name}", parents, fn=fn, narrow=False)
        node.task_kind = "native"
        node.owner = self
        node.sig = ("native", "voidCall", tok, *(node_sig(p) for p in parents))
        frame = IDataFrame(self, node)

        def task_fn(memo):
            worker.engine.evaluate(node, memo=memo)
            return out_cell.get("value")

        return frame._submit("voidCall", task_fn=task_fn, job=job)

    def void_call(self, fn_name, df: IDataFrame | None = None, **params):
        """Run a native app for effect (paper's voidCall) — facade over the
        async path. Params pass through verbatim (an app param named "job"
        reaches the app's context; only the async variant reserves it)."""
        return self._void_call_task(fn_name, df, params, None).result()

    def call(self, fn_name, df: IDataFrame | None = None, **params) -> IDataFrame:
        """Run a native app returning rows → IDataFrame (paper's call).

        The node is a first-class lineage citizen: the child IContext is
        bound when the task EXECUTES (late ``set_var`` updates are visible),
        and the (app, params) token is part of ``node.sig`` so downstream
        plan/capacity caches key on the actual call."""
        app, name, params, tok = self._resolve_app(fn_name, params)
        parents = [df.node] if df is not None else []
        worker = self

        def fn(parent_results):
            ctx = worker.context.bind(params)  # execution-time binding
            args = worker._native_args(ctx, parent_results)
            with span("native:" + name, call="call"):
                out = app(ctx, *args)
            if comm_mod.is_handle(out):
                # app handed back an in-flight collective: keep it
                # nonblocking — chain the Block adaptation onto the handle
                # and let the engine/scheduler await it (dag.py _compute)
                return out.chain(
                    lambda v: [v] if isinstance(v, Block) else [Block(*v)])
            if isinstance(out, Block):
                return [out]
            data, valid = out
            return [Block(data, valid)]

        node = TaskNode(f"call:{name}", parents, fn=fn, narrow=False)
        node.task_kind = "native"
        node.owner = self
        node.sig = ("native", "call", tok, *(node_sig(p) for p in parents))
        return IDataFrame(self, node)

    def call_partitions(self, fn_name, df: IDataFrame, **params) -> IDataFrame:
        """Partition-preserving native call: the app runs once per block
        with the worker communicator — no ``_merged()`` collapse. The node
        is narrow with block-wise lineage, so it composes with caching,
        stage boundaries, and ``kill_block`` repair (only the lost block
        re-runs the app)."""
        app, name, params, tok = self._resolve_app(fn_name, params)
        worker = self

        def block_fn(parent_blocks):
            ctx = worker.context.bind(params)  # execution-time binding
            b = parent_blocks[0]
            with span("native:" + name, call="callPartitions"):
                out = app(ctx, b.data, b.valid)
            if comm_mod.is_handle(out):
                out = out.wait()  # block-wise lineage is the sync point here
            if isinstance(out, Block):
                return out
            data, valid = out
            return Block(data, valid)

        node = TaskNode(
            f"callPartitions:{name}", [df.node], block_fn=block_fn, narrow=True
        )
        node.task_kind = "native"
        node.owner = self
        node.sig = ("native", "callPartitions", tok, node_sig(df.node))
        return IDataFrame(self, node)

    voidCall = void_call
    voidCallAsync = void_call_async
    callPartitions = call_partitions

    # ------------------------------------------------------------------
    # spark-mode pipe simulation (paper §2.1: system pipes outside the JVM)
    # ------------------------------------------------------------------
    # PySpark serializes RDD elements through the JVM↔worker pipe in pickle
    # batches (default batchSize=1024) — per-ELEMENT object serialization,
    # not one bulk buffer. That is the cost the paper measures (§2.1, §6.2);
    # we model it faithfully.
    _PIPE_BATCH = 1024

    def _pipe_block(self, b: Block) -> Block:
        """Charge the pipe cost: device→host, per-element pickle of every
        (valid) row in PySpark-sized batches, host→device. The data itself is
        returned unchanged — this models serialization cost, not semantics."""
        data = jax.device_get(b.data)
        valid = np.asarray(jax.device_get(b.valid))
        leaves, _ = jax.tree_util.tree_flatten(data)
        idx = np.nonzero(valid)[0]
        for lo in range(0, len(idx), self._PIPE_BATCH):
            sel = idx[lo : lo + self._PIPE_BATCH]
            batch = [[np.asarray(l[i]) for l in leaves] for i in sel]
            pickle.loads(pickle.dumps(batch))  # the JVM↔worker pipe
        return Block(jax.tree.map(self._put, data), self._put(valid))

    def _pipe_wrap(self, block_fn):
        def wrapped(parent_blocks):
            return self._pipe_block(block_fn(parent_blocks))

        return wrapped

    def _pipe_wrap_wide(self, node_fn):
        """Spark's shuffle path: results serialize through the host (JVM)."""

        def wrapped(parent_results):
            return [self._pipe_block(b) for b in node_fn(parent_results)]

        return wrapped
