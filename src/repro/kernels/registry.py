"""Kernel tier: mode resolution, capability probe, autotune memo
(docs/kernels.md, DESIGN.md §11).

The shuffle engine's reduceByKey stage (core/shuffle_plan.py) has one
Pallas kernel, ``segment_reduce``, beside a plain-JAX oracle that is
always available. This module decides, per reduceByKey node, which one
runs:

* **Mode** (``ignis.kernels``): ``auto`` uses the compiled kernel where
  the backend supports it and the plain-JAX fallback everywhere else (an
  interpreted kernel is strictly slower than the jnp oracle, so auto
  never interprets); ``on`` forces the kernel (compiled where available,
  ``interpret=True`` otherwise); ``interpret`` forces interpret mode
  (the CI conformance path); ``off`` forces the fallback.
* **Capability probe**: a tiny invocation of the kernel per (interpret,
  backend), cached. An interpreted kernel that fails its probe degrades
  to the fallback; a compiled one raises ``KernelUnavailable`` with the
  compiler's message, and so does a failed autotune sweep of a compiled
  kernel (``degrade``): a chip run never quietly leaves the kernel path
  it claims to run. The ``kernel.capability`` fault site fires on every
  selection so chaos tests can force mid-job degradation.
* **Autotune memo**: best block size per (aval, op) key, found by the
  shuffle engine's timed sweep (``ShuffleManager._tune_reduce``) over
  ``ignis.kernels.blocks`` candidates. The memo is an LRU with
  single-builder discipline (per-key in-flight Event, same pattern as
  comm.py's collective plan cache): concurrent misses on one key cost
  exactly one sweep. Tuned blocks feed the wide-plan cache key, so a
  repeat lineage pays zero re-tunes and zero recompiles.

Selection results and tune counts surface as ``kernel_hits`` /
``kernel_fallbacks`` / ``autotune_runs`` / ``autotune_evictions`` in
``worker.shuffle_stats()`` and ``df.explain()``.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import Counters
from repro.core.properties import REGISTRY as PROPS
from repro.kernels.segment_reduce.lanes import TILE

#: dtypes the kernel tier computes natively (bool rides as i32)
SUPPORTED_DTYPES = ("float32", "int32")

#: autotune candidates: rows per grid step, whole (8, 128) tiles each
DEFAULT_BLOCKS = PROPS["ignis.kernels.blocks"].default


def compiled_backend() -> bool:
    """True where pl.pallas_call lowers to a real Mosaic kernel."""
    return jax.default_backend() == "tpu"


class KernelUnavailable(RuntimeError):
    """A compiled kernel failed its probe or its autotune sweep."""


#: the one kernel the tier selects
KERNEL = "segment_reduce"


@dataclass(frozen=True)
class Selection:
    """A resolved kernel choice: interpreted or compiled."""

    interpret: bool

    def describe(self) -> str:
        return f"{KERNEL}[{'interpret' if self.interpret else 'compiled'}]"


# ---------------------------------------------------------------------------
# capability probe: one tiny invocation of the kernel
# ---------------------------------------------------------------------------


def _probe_segment_reduce(interpret: bool):
    from repro.kernels.segment_reduce.segment_reduce import segment_reduce_fwd

    v = jnp.zeros((TILE, 1), jnp.float32)
    hb = jnp.ones((TILE,), bool)
    jax.block_until_ready(
        segment_reduce_fwd(v, hb, op="sum", block=TILE, interpret=interpret))


# ---------------------------------------------------------------------------
# builtin-op recognition: which reduce fns the kernel tier can take over
# ---------------------------------------------------------------------------

_PRIM_OPS = {"add": "sum", "max": "max", "min": "min"}


def builtin_reduce_op(fn, identity, value) -> Optional[str]:
    """Recognize a reduceByKey fn as a builtin sum/max/min the segment
    kernel implements, or None (→ jnp-oracle fallback).

    Eligibility (anything else falls back, never errors): the value is a
    single array leaf of a supported dtype with ndim ≤ 2, the identity is
    a single scalar leaf, and ``fn`` traces to exactly one add/max/min
    primitive applied to its two arguments with no dtype change. A
    recognized fn is numerically the same primitive the kernel applies,
    which is what makes the kernel path bit-identical for exact ops
    (docs/kernels.md).
    """
    leaves = jax.tree_util.tree_leaves(value)
    ileaves = jax.tree_util.tree_leaves(identity)
    if len(leaves) != 1 or len(ileaves) != 1 or np.ndim(ileaves[0]) != 0:
        return None
    leaf = leaves[0]
    dtype = getattr(leaf, "dtype", None)
    if dtype is None or str(dtype) not in SUPPORTED_DTYPES or leaf.ndim > 2:
        return None
    try:
        jaxpr = jax.make_jaxpr(fn)(jnp.zeros((), dtype), jnp.zeros((), dtype))
    except Exception:
        return None
    eqns = jaxpr.jaxpr.eqns
    if len(eqns) != 1:
        return None
    eqn = eqns[0]
    op = _PRIM_OPS.get(eqn.primitive.name)
    if op is None or len(eqn.invars) != 2:
        return None
    # both operands must be the fn's own arguments (rejects a+const, a+a)
    if {id(v) for v in eqn.invars} != {id(v) for v in jaxpr.jaxpr.invars}:
        return None
    out = jaxpr.jaxpr.outvars
    if len(out) != 1 or out[0].aval.dtype != dtype or out[0].aval.shape != ():
        return None
    return op


class KernelRegistry:
    """Per-worker kernel capability + autotune state (one per
    ShuffleManager; thread-safe — gang tasks share it)."""

    MODES = ("auto", "on", "off", "interpret")

    def __init__(self, mode: str = "auto", blocks=DEFAULT_BLOCKS,
                 tune_cache_size: int = 512):
        mode = str(mode).strip().lower()
        if mode not in self.MODES:
            raise ValueError(f"ignis.kernels={mode!r}: expected one of {self.MODES}")
        self.mode = mode
        if isinstance(blocks, str):
            blocks = [int(b) for b in blocks.replace(",", " ").split()]
        self.blocks = tuple(int(b) for b in blocks) or (256,)
        self.tune_cache_size = int(tune_cache_size)
        self._lock = threading.Lock()
        self._probe_cache: dict = {}
        self._tunes: "OrderedDict[tuple, int]" = OrderedDict()
        self._tuning: dict = {}  # key → Event while a sweep is in flight
        self.stats = Counters("kernels", {
            "kernel_hits": 0,        # wide nodes that ran kernel-backed
            "kernel_fallbacks": 0,   # kernel-eligible nodes on the jnp oracle
            "autotune_runs": 0,      # block-size sweeps performed
            "autotune_evictions": 0,
        })

    def _bump(self, key: str, n: int = 1):
        with self._lock:
            self.stats[key] += n

    # ------------------------------------------------------------------
    # selection
    # ------------------------------------------------------------------
    def _probe(self, interpret: bool) -> bool:
        key = (interpret, jax.default_backend())
        with self._lock:
            if key in self._probe_cache:
                return self._probe_cache[key]
        try:
            _probe_segment_reduce(interpret)
            ok = True
        except Exception as e:
            if not interpret:  # nothing cached: the next selection re-probes
                raise KernelUnavailable(
                    f"{KERNEL}: compiled probe failed on "
                    f"{jax.default_backend()}: {e}") from e
            ok = False
        with self._lock:
            self._probe_cache[key] = ok
        return ok

    def select(self) -> Optional[Selection]:
        """Resolve one kernel-eligible reduceByKey node. None → plain-JAX
        fallback (always available, bit-identical for exact ops).

        A ``kernel.capability`` fault or a failed interpreted probe
        degrades to the fallback rather than erroring — capability loss
        mid-job must not kill the job (unlike ``kernel.stage``, which is a
        task fault the scheduler retries via lineage). A failed compiled
        probe raises ``KernelUnavailable``.
        """
        # deferred import: repro.core.shuffle_plan imports this module at
        # class-definition time, so a module-level core import would cycle
        from repro.core import faults

        if self.mode == "off":
            return self._fallback()
        try:
            faults.check("kernel.capability", kernel=KERNEL)
        except faults.FaultInjected:
            return self._fallback()
        if self.mode == "auto":
            if not compiled_backend():
                # interpreted Pallas is strictly slower than the jnp
                # oracle — auto never interprets (docs/kernels.md)
                return self._fallback()
            interpret = False
        elif self.mode == "interpret":
            interpret = True
        else:  # "on": compiled where the backend supports it
            interpret = not compiled_backend()
        if not self._probe(interpret):
            return self._fallback()
        self._bump("kernel_hits")
        return Selection(interpret)

    def _fallback(self) -> None:
        self._bump("kernel_fallbacks")
        return None

    def demote(self):
        """Re-book the last counted hit as a fallback — a post-selection
        step (e.g. the autotune sweep) failed and the caller degraded to
        the plain-JAX path after all."""
        with self._lock:
            self.stats["kernel_hits"] -= 1
            self.stats["kernel_fallbacks"] += 1

    def degrade(self, sel: Selection, exc: Exception):
        """A post-selection step (the autotune sweep) of ``sel`` failed.
        Interpreted: ``demote`` and let the caller take the plain-JAX path.
        Compiled: raise ``KernelUnavailable`` with the failure's message."""
        if not sel.interpret:
            raise KernelUnavailable(
                f"{KERNEL}: compiled autotune sweep failed on "
                f"{jax.default_backend()}: {exc}") from exc
        self.demote()

    # ------------------------------------------------------------------
    # autotune memo (single-builder, LRU — comm.py plan-cache discipline)
    # ------------------------------------------------------------------
    def tune(self, key: tuple, candidates, timer: Callable[[int], float]) -> int:
        """Best block size for ``key``; memoised. ``timer(block)`` returns
        seconds for one representative invocation at that block size.
        Concurrent misses on one key cost exactly one sweep; a failed
        sweep unparks the waiters (one of them re-tunes)."""
        while True:
            with self._lock:
                b = self._tunes.get(key)
                if b is not None:
                    self._tunes.move_to_end(key)
                    return b
                building = self._tuning.get(key)
                if building is None:
                    building = self._tuning[key] = threading.Event()
                    break
            building.wait()
        try:
            cands = sorted({int(c) for c in candidates})
            if not cands:
                raise ValueError("autotune: empty candidate set")
            best, best_t = cands[0], float("inf")
            if len(cands) > 1:  # a single candidate needs no timing
                for c in cands:
                    t = timer(c)
                    if t < best_t:
                        best, best_t = c, t
            with self._lock:
                self.stats["autotune_runs"] += 1
                self._tunes[key] = best
                while len(self._tunes) > self.tune_cache_size:
                    self._tunes.popitem(last=False)
                    self.stats["autotune_evictions"] += 1
            return best
        finally:
            with self._lock:
                self._tuning.pop(key, None)
            building.set()

    def describe(self) -> str:
        s = self.stats
        return (f"mode={self.mode} hits={s['kernel_hits']} "
                f"fallbacks={s['kernel_fallbacks']} "
                f"autotune_runs={s['autotune_runs']} "
                f"autotune_evictions={s['autotune_evictions']} "
                f"tuned_keys={len(self._tunes)}")
