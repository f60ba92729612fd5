"""The "MPI" layer: nonblocking, persistent collectives over shard_map
(paper §2.2, §3.6; UCC model — SNIPPETS.md §3, docs/collectives.md).

Every routine takes an IContext (the communicator) and operates on arrays
sharded along the context axis. These are the primitives the executor module
builds the dataflow operators out of, and the ones native SPMD apps call —
the analogue of MPICH under both worlds, with jax.lax collectives on
ICI/DCN instead of send/recv on Infiniband.

Three call shapes per collective, mirroring UCC's design goals:

* **blocking** — ``allreduce(ctx, x)``: dispatch + ``wait()``; the result is
  ready when the call returns.
* **nonblocking** — ``iallreduce(ctx, x) -> CollHandle``: the MPI_Iallreduce
  shape. The collective is dispatched (jax async dispatch = the wire
  transfer in flight) and the handle is the future; ``handle.wait()`` is
  MPI_Wait, ``handle.test()`` is MPI_Test. The job scheduler and the DAG
  engine await handles instead of blocking a worker thread, so independent
  branches overlap compute with communication (core/job.py, core/dag.py).
* **persistent** — ``persistent(ctx, "allreduce", x) -> CollPlan``: the
  MPI_*_init / MPI_Start shape (UCC: "init once and invoke multiple
  times"). The collective's shard_map is traced and jit-compiled ONCE per
  (collective, static args, operand avals, communicator mesh) and cached in
  a process-wide LRU (the collective analogue of the wide-plan cache,
  DESIGN.md §6/§10); ``plan.start(x)`` re-invokes the compiled plan with no
  Python-side retracing. The i*/blocking entry points route through the
  same cache, so every repeated collective is init-once/invoke-many
  automatically — hit/miss telemetry surfaces in ``worker.shuffle_stats()``
  and the scheduler stats (``comm_stats()`` is the raw view).

Every collective binds to the context's OWN mesh — hand it a group context
(``IContext.split``/``group``, docs/collectives.md) and it runs on the
group's sub-mesh and axis, never touching executors outside the group.
Inputs are placed onto the context's mesh first (``IContext.place``, a
no-op when already there), so an array produced under one communicator can
enter a collective on another — the device_put IS the inter-group reshard
edge. Handles are group-portable the same way: a handle started on one
communicator may be awaited from a thread bound to another (the result is
committed to the issuing group's mesh; consumers reshard on ingress).

Fault injection (docs/fault_tolerance.md): ``handle.wait()`` of a still-
pending handle passes the ``comm.handle`` site, so chaos plans can kill a
collective between dispatch and completion; the scheduler retries the
owning task through the job's shared memo (core/job.py).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat, faults
from repro.core.context import IContext
from repro.core.metrics import Counters
from repro.profile.spans import first_call

_handle_ids = itertools.count()


# ---------------------------------------------------------------------------
# nonblocking handles (MPI_Request / ucc_coll_req)
# ---------------------------------------------------------------------------


class CollHandle:
    """Future for a dispatched collective.

    The operation is already in flight when the handle exists (jax async
    dispatch); ``wait()`` blocks until the device result is ready and
    returns it. ``wait()`` is idempotent — a second wait returns the same
    completed value without re-entering the fault site (MPI semantics:
    waiting on an inactive request is a no-op). ``test()`` is the
    nonblocking completion probe.

    Handles created inside a job task are tracked (``track()``); any handle
    the task never awaited is drained by the scheduler at task end, so a
    leaked in-flight collective can neither outlive its job silently nor
    escape fault accounting (the never-awaited-at-job-end chaos rule).

    Completion is thread-safe: handles are group-portable across threads
    (module docstring), so ``wait``/``test``/``chain`` may race — a
    per-handle lock makes exactly one thread finalise (apply ``_transform``
    and publish the value); every other waiter returns the same completed
    value, never a double-transformed one.
    """

    __slots__ = ("coll", "ctx", "id", "_value", "_transform", "_done", "_scope",
                 "_lock")

    def __init__(self, coll: str, ctx, value, transform: Optional[Callable] = None):
        self.coll = coll
        self.ctx = ctx  # the issuing communicator (group-portable: carried here)
        self.id = next(_handle_ids)
        self._value = value
        self._transform = transform
        self._done = False
        self._lock = threading.Lock()
        scope = getattr(_scopes, "pending", None)
        self._scope = scope
        if scope is not None:
            scope.append(self)
        _engine.stats_bump("handles_created")

    # -- introspection ---------------------------------------------------
    @property
    def pending(self) -> bool:
        return not self._done

    def done(self) -> bool:
        """MPI_Test's completion half: True once the device result is ready
        (never blocks)."""
        if self._done:
            return True
        return all(
            getattr(l, "is_ready", lambda: True)()
            for l in jax.tree_util.tree_leaves(self._value)
        )

    def test(self):
        """MPI_Test: ``(True, value)`` when complete, ``(False, None)``
        otherwise. Completion via test() finalises the handle like wait()."""
        if not self._done and not self.done():
            return False, None
        return True, self.wait()

    # -- completion ------------------------------------------------------
    def wait(self, _phase: str = "wait"):
        """MPI_Wait: block until the collective completes, return its value.
        Idempotent after completion. The ``comm.handle`` fault site fires
        here (phase="wait", or "flush" for the scheduler's end-of-task
        drain) while the handle is still pending — an injected failure
        models losing the transfer mid-flight, and leaves the handle
        pending so a scheduler retry re-issues the collective."""
        if self._done:  # fast path: _done is published AFTER _value (below)
            return self._value
        with self._lock:
            if self._done:  # another thread finalised while we waited
                return self._value
            faults.check("comm.handle", coll=self.coll, phase=_phase)
            value = jax.block_until_ready(self._value)
            if self._transform is not None:
                value = self._transform(value)
            self._value = value
            self._transform = None
            self._done = True  # publish: value must be stored first
            scope = self._scope
            if scope is not None:
                self._scope = None
                try:
                    scope.remove(self)
                except ValueError:
                    pass
        _engine.stats_bump("handles_awaited")
        return self._value

    def chain(self, fn: Callable) -> "CollHandle":
        """Append a host-side transform applied to the awaited value (used
        by the driver layer to adapt app results without forcing a wait)."""
        with self._lock:
            if self._done:
                self._value = fn(self._value)
                return self
            prev = self._transform
            self._transform = fn if prev is None else (lambda v: fn(prev(v)))
            return self

    def __repr__(self):
        state = "done" if self._done else "pending"
        return f"<CollHandle #{self.id} {self.coll} [{state}]>"


def is_handle(x) -> bool:
    return isinstance(x, CollHandle)


def wait_all(handles) -> list:
    """MPI_Waitall over an iterable of handles (completion in given order)."""
    return [h.wait() for h in handles]


# -- task-scoped handle tracking (the never-awaited-at-job-end rule) --------

_scopes = threading.local()


@contextlib.contextmanager
def track():
    """Collect every handle created on this thread inside the block. The job
    scheduler wraps each task attempt in one ``track()`` scope and drains
    whatever is still pending when the task function returns
    (core/job.py)."""
    prev = getattr(_scopes, "pending", None)
    cur: list[CollHandle] = []
    _scopes.pending = cur
    try:
        yield cur
    finally:
        _scopes.pending = prev


# ---------------------------------------------------------------------------
# persistent-plan engine (init once / invoke many — UCC design goal)
# ---------------------------------------------------------------------------


class CommEngine:
    """Process-wide persistent collective plans + telemetry.

    One compiled plan per (collective, static args, operand avals, mesh) in
    an LRU — keyed like the shuffle engine's wide-plan cache (DESIGN.md §6)
    so a plan traced for a p=4 group never serves the p=8 world. The engine
    is process-wide (not per-worker) because a collective's identity is its
    communicator, not the worker that issued it: two workers sharing one
    mesh share plans, exactly as two MPI libraries sharing one fabric
    would share UCC teams."""

    def __init__(self, plan_cache_size: int = 128):
        self.plan_cache_size = plan_cache_size
        self._plans: "OrderedDict[tuple, Callable]" = OrderedDict()
        self._building: dict = {}  # key -> Event: trace+jit in flight
        self._lock = threading.Lock()
        self.stats = Counters("coll", {
            "coll_calls": 0,          # collectives dispatched (any shape)
            "coll_plan_hits": 0,      # persistent-plan cache hits
            "coll_plan_misses": 0,    # traces+compiles (init-once events)
            "coll_plan_evictions": 0,
            "handles_created": 0,
            "handles_awaited": 0,
        })

    def stats_bump(self, key: str, n: int = 1):
        with self._lock:
            self.stats[key] += n

    def plan(self, key: tuple, builder: Callable[[], Callable]) -> Callable:
        """The compiled plan for ``key``, building (trace + jit) on miss.

        Exactly one thread builds a given key: a concurrent miss parks on
        the builder's in-flight event and re-reads the cache, so two
        threads racing the same collective cost one trace total and
        ``coll_plan_misses`` counts distinct init-once events (the
        ``recompiles=0`` gate in bench_collectives relies on this). The
        build itself runs outside the lock — tracing can re-enter plan()
        (nested collectives) and must not self-deadlock."""
        while True:
            with self._lock:
                fn = self._plans.get(key)
                if fn is not None:
                    self._plans.move_to_end(key)
                    self.stats["coll_plan_hits"] += 1
                    return fn
                building = self._building.get(key)
                if building is None:
                    self._building[key] = building = threading.Event()
                    self.stats["coll_plan_misses"] += 1
                    break
            building.wait()  # builder finished (or failed) → re-read cache
        try:
            fn = jax.jit(builder())
        except BaseException:
            # failed build: unpark waiters with the cache still empty so
            # one of them (or a retry) becomes the next builder
            with self._lock:
                self._building.pop(key, None)
            building.set()
            raise
        with self._lock:
            self._plans[key] = fn
            self._building.pop(key, None)
            while len(self._plans) > self.plan_cache_size:
                self._plans.popitem(last=False)
                self.stats["coll_plan_evictions"] += 1
        building.set()
        return first_call("coll", fn)

    def clear(self):
        """Drop every compiled plan (benchmarks use this to measure the
        init-once cost; correctness never depends on cache state)."""
        with self._lock:
            self._plans.clear()


_engine = CommEngine()


def engine() -> CommEngine:
    return _engine


def comm_stats() -> dict:
    """Snapshot of the collective engine telemetry (also merged into
    ``worker.shuffle_stats()``)."""
    with _engine._lock:
        return dict(_engine.stats)


def _aval(x) -> tuple:
    return tuple(
        (tuple(l.shape), str(l.dtype)) for l in jax.tree_util.tree_leaves(x)
    )


class CollPlan:
    """An initialised persistent collective (MPI_Allreduce_init analogue):
    ``start()`` dispatches one invocation and returns its ``CollHandle``
    (MPI_Start); calling the plan is the blocking facade. The compiled
    kernel is shared through the process-wide plan cache, so equivalent
    plans (same collective, statics, avals, mesh) cost one trace total."""

    __slots__ = ("coll", "ctx", "_fn", "_transform", "_prep")

    def __init__(self, coll: str, ctx, fn: Callable, transform=None, prep=None):
        self.coll = coll
        self.ctx = ctx
        self._fn = fn
        self._transform = transform
        self._prep = prep  # host-side operand validation/placement

    def start(self, *operands) -> CollHandle:
        """Dispatch one invocation (MPI_Start) → nonblocking handle."""
        if self._prep is not None:
            operands = self._prep(*operands)
        _engine.stats_bump("coll_calls")
        return CollHandle(self.coll, self.ctx, self._fn(*operands),
                          transform=self._transform)

    def __call__(self, *operands):
        return self.start(*operands).wait()


# ---------------------------------------------------------------------------
# collective builders: each returns (traced_fn_builder, transform, prep)
# ---------------------------------------------------------------------------


def _sharded(ctx):  # leading dim sharded over the context axis
    return P(ctx.axis)


def _placed(ctx: IContext, x, spec=None):
    """Commit ``x`` to the context's mesh (no-op when already resident) —
    delegates to ``IContext.place`` so every subsystem shares one reshard
    edge (docs/collectives.md)."""
    return ctx.place(x, spec)


def _smap(ctx: IContext, f, in_specs, out_specs):
    return compat.shard_map(f, mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs)


_REDUCERS = {"sum": (jnp.sum, jax.lax.psum),
             "max": (jnp.max, jax.lax.pmax),
             "min": (jnp.min, jax.lax.pmin)}


def _plan_for(ctx: IContext, coll: str, statics: tuple, avals: tuple,
              builder: Callable[[], Callable], transform=None) -> CollPlan:
    fn = _engine.plan((coll, statics, avals, ctx.mesh, ctx.axis), builder)
    return CollPlan(coll, ctx, fn, transform=transform,
                    prep=lambda *ops: tuple(_placed(ctx, o) for o in ops))


def _allreduce_plan(ctx: IContext, x, op: str) -> CollPlan:
    if op not in _REDUCERS:
        raise ValueError(f"allreduce op must be one of {sorted(_REDUCERS)}, got {op!r}")
    local, wire = _REDUCERS[op]

    def builder():
        def f(xs):
            return wire(local(xs, axis=0), ctx.axis)

        return _smap(ctx, f, (_sharded(ctx),), P())

    return _plan_for(ctx, "allreduce", (op,), _aval(x), builder)


def _gather_plan(ctx: IContext, x) -> CollPlan:
    def builder():
        def f(xs):
            return jax.lax.all_gather(xs, ctx.axis, tiled=True)

        return _smap(ctx, f, (_sharded(ctx),), P())

    return _plan_for(ctx, "gather", (), _aval(x), builder)


def _alltoall_check(ctx: IContext, x):
    p = ctx.executors
    n = x.shape[0]
    if n % p or (n // p) % p:
        # a silent reshape here would regroup rows to the WRONG peers
        raise ValueError(
            f"alltoall needs the local row count divisible by the communicator "
            f"size: total {n} rows over {p} executors gives "
            f"{n / p:g} local rows, which must be a multiple of {p}")


def _alltoall_plan(ctx: IContext, x) -> CollPlan:
    """MPI_Alltoall. x: (p·k, …) axis-sharded on dim 0; shard i holds the
    (k, …) rows destined for each peer in order. Returns same shape with
    rows regrouped by source."""
    _alltoall_check(ctx, x)  # BEFORE any mesh work: invalid shapes must not fly
    p = ctx.executors

    def builder():
        def f(xs):  # xs local: (k_total, …) with k_total = n/p — regroup to (p, k)
            k = xs.shape[0] // p
            y = xs.reshape(p, k, *xs.shape[1:])
            y = jax.lax.all_to_all(y, ctx.axis, split_axis=0, concat_axis=0,
                                   tiled=False)
            return y.reshape(p * k, *xs.shape[1:])

        return _smap(ctx, f, (_sharded(ctx),), _sharded(ctx))

    return _plan_for(ctx, "alltoall", (), _aval(x), builder)


def _ppermute_plan(ctx: IContext, x, shift: int) -> CollPlan:
    p = ctx.executors
    perm = [(i, (i + shift) % p) for i in range(p)]

    def builder():
        def f(xs):
            return jax.lax.ppermute(xs, ctx.axis, perm)

        return _smap(ctx, f, (_sharded(ctx),), _sharded(ctx))

    return _plan_for(ctx, "ppermute", (shift,), _aval(x), builder)


def _exscan_plan(ctx: IContext, x, op: str) -> CollPlan:
    """MPI_Exscan (exclusive prefix over executor ranks) of per-shard
    scalars. x: (p,) axis-sharded (one scalar per executor)."""
    if op != "sum":
        raise ValueError(f"exscan supports op='sum' only, got {op!r}")

    def builder():
        def f(xs):
            all_ = jax.lax.all_gather(xs, ctx.axis, tiled=True)  # (p,)
            idx = jax.lax.axis_index(ctx.axis)
            mask = jnp.arange(all_.shape[0]) < idx
            return jnp.sum(all_ * mask, axis=0, keepdims=True)

        return _smap(ctx, f, (_sharded(ctx),), _sharded(ctx))

    return _plan_for(ctx, "exscan", (op,), _aval(x), builder)


def _barrier_plan(ctx: IContext) -> CollPlan:
    z = jnp.zeros((ctx.executors,), jnp.int32)

    def builder():
        def f(xs):
            return jax.lax.psum(jnp.sum(xs, axis=0), ctx.axis)

        return _smap(ctx, f, (_sharded(ctx),), P())

    return CollPlan(
        "barrier", ctx,
        lambda: _engine.plan(("barrier", (), _aval(z), ctx.mesh, ctx.axis),
                             builder)(_placed(ctx, z)),
        transform=lambda _v: None)


# ---------------------------------------------------------------------------
# the persistent API (init once / invoke many)
# ---------------------------------------------------------------------------

_PLAN_BUILDERS = {
    "allreduce": lambda ctx, x, op="sum": _allreduce_plan(ctx, x, op),
    "reduce": lambda ctx, x, op="sum": _allreduce_plan(ctx, x, op),
    "gather": lambda ctx, x: _gather_plan(ctx, x),
    "alltoall": lambda ctx, x: _alltoall_plan(ctx, x),
    "ppermute": lambda ctx, x, shift=1: _ppermute_plan(ctx, x, shift),
    "exscan": lambda ctx, x, op="sum": _exscan_plan(ctx, x, op),
}


def persistent(ctx: IContext, coll: str, x=None, **statics) -> CollPlan:
    """Initialise a persistent collective plan for operands shaped like
    ``x`` (MPI_*_init): ``plan.start(x)`` dispatches an invocation,
    ``plan(x)`` is the blocking facade. Plans are cheap to re-create — the
    compiled kernel lives in the process-wide LRU, so init-once is a cache
    property, not an object-lifetime obligation."""
    if coll == "barrier":
        return _barrier_plan(ctx)
    if coll == "bcast":
        return CollPlan("bcast", ctx, lambda v: _placed(ctx, v, P()))
    if coll == "scatter":
        return CollPlan("scatter", ctx, lambda v: _placed(ctx, v))
    builder = _PLAN_BUILDERS.get(coll)
    if builder is None:
        raise ValueError(f"unknown collective {coll!r} "
                         f"(have {sorted(_PLAN_BUILDERS) + ['barrier', 'bcast', 'scatter']})")
    if x is None:
        raise ValueError(f"persistent({coll!r}) needs a prototype operand")
    return builder(ctx, x, **statics)


def persistent_program(tag: str, mesh, statics: tuple,
                       builder: Callable[[], Callable]) -> Callable:
    """Init-once/invoke-many plan for a whole SPMD program (a native app's
    shard_map body): the same LRU + telemetry as single-collective plans,
    keyed by (tag, statics, mesh). Native apps route their hot loops
    through this so repeated calls skip the Python-side re-trace — which
    is what lets a native branch genuinely overlap a dataflow branch in an
    async job (the re-trace is GIL-bound; compiled execution is not)."""
    return _engine.plan(("spmd", tag, statics, mesh), builder)


# ---------------------------------------------------------------------------
# nonblocking collectives (MPI_I* — dispatch now, CollHandle as the future)
# ---------------------------------------------------------------------------


def iallreduce(ctx: IContext, x, op: str = "sum") -> CollHandle:
    """MPI_Iallreduce over executor shards: x is axis-sharded on dim 0."""
    return _allreduce_plan(ctx, x, op).start(x)


def ireduce(ctx: IContext, x, op: str = "sum") -> CollHandle:
    """MPI_Ireduce (root=driver): same wire pattern as allreduce on TPU."""
    return iallreduce(ctx, x, op)


def ibcast(ctx: IContext, x) -> CollHandle:
    """MPI_Ibcast: replicate a driver value across executors."""
    _engine.stats_bump("coll_calls")
    return CollHandle("bcast", ctx, _placed(ctx, x, P()))


def igather(ctx: IContext, x) -> CollHandle:
    """MPI_Iallgather: axis-sharded (n, …) → replicated (n, …)."""
    return _gather_plan(ctx, x).start(x)


def iscatter(ctx: IContext, x) -> CollHandle:
    """MPI_Iscatter: replicated (n, …) → axis-sharded (n, …)."""
    _engine.stats_bump("coll_calls")
    return CollHandle("scatter", ctx, _placed(ctx, x))


def ialltoall(ctx: IContext, x) -> CollHandle:
    """MPI_Ialltoall — shape validation is eager (the ValueError fires at
    dispatch, not at wait: an invalid exchange must never enter flight)."""
    return _alltoall_plan(ctx, x).start(x)


def ippermute(ctx: IContext, x, shift: int = 1) -> CollHandle:
    """MPI_Isend/Irecv ring: shard i's rows go to shard (i+shift) % p."""
    return _ppermute_plan(ctx, x, shift).start(x)


def iexscan(ctx: IContext, x, op: str = "sum") -> CollHandle:
    return _exscan_plan(ctx, x, op).start(x)


def ibarrier(ctx: IContext) -> CollHandle:
    """MPI_Ibarrier: a zero-byte allreduce in flight; wait() returns None."""
    return _barrier_plan(ctx).start()


# ---------------------------------------------------------------------------
# blocking facades (each is literally i*(…).wait())
# ---------------------------------------------------------------------------


def allreduce(ctx: IContext, x, op: str = "sum"):
    """MPI_Allreduce: blocking facade over ``iallreduce``."""
    return iallreduce(ctx, x, op).wait()


def reduce(ctx: IContext, x, op: str = "sum"):
    """MPI_Reduce (root=driver): same wire pattern as allreduce on TPU."""
    return allreduce(ctx, x, op)


def bcast(ctx: IContext, x):
    """MPI_Bcast: replicate a driver value across executors."""
    return ibcast(ctx, x).wait()


def gather(ctx: IContext, x):
    """MPI_Allgather: axis-sharded (n, …) → replicated (n, …)."""
    return igather(ctx, x).wait()


def scatter(ctx: IContext, x):
    """MPI_Scatter: replicated (n, …) → axis-sharded (n, …)."""
    return iscatter(ctx, x).wait()


def alltoall(ctx: IContext, x):
    """MPI_Alltoall (see ``ialltoall`` for the validation contract)."""
    return ialltoall(ctx, x).wait()


def ppermute(ctx: IContext, x, shift: int = 1):
    """MPI_Sendrecv ring: shard i's rows go to shard (i+shift) % p."""
    return ippermute(ctx, x, shift).wait()


def barrier(ctx: IContext):
    """MPI_Barrier: a zero-byte allreduce, blocked on."""
    ibarrier(ctx).wait()


def exscan(ctx: IContext, x, op: str = "sum"):
    """MPI_Exscan (exclusive prefix over executor ranks) of per-shard scalars."""
    return iexscan(ctx, x, op).wait()


# ---------------------------------------------------------------------------
# helpers for data placement
# ---------------------------------------------------------------------------


def shard_rows(ctx: IContext, x):
    """Place an (N, …) array sharded by rows over the executor axis."""
    return _placed(ctx, x)


def replicate(ctx: IContext, x):
    return _placed(ctx, x, P())
