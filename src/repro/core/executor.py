"""Executor module: block-level implementations of the dataflow operators
(paper §3.6). Narrow ops here; wide (shuffle-backed) ops in shuffle.py.

User functions are jnp-traceable row functions, vmapped over the block. A
negative/boolean mask carries filter results (fixed shapes — no dynamic
compaction on device).
"""
from __future__ import annotations

import threading
import weakref
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.partition import Block
from repro.profile.spans import first_call

# jit cache keyed on the user fn object: a dataframe op's fn is created once
# at graph-build time, so re-evaluating the same node hits the trace cache
# (compute-heavy row fns — e.g. Minebench's SHA-256 — would otherwise run
# eagerly op-by-op).
_VMAP_JIT: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_misses = threading.local()


def vmap_misses() -> int:
    """Misses of the jitted-map cache on this thread so far (the engine
    adds the ones its blocks cause to ``stages/vmap_misses``)."""
    return getattr(_misses, "n", 0)


def _vmapped(fn: Callable) -> Callable:
    try:
        j = _VMAP_JIT.get(fn)
    except TypeError:  # unhashable/unweakrefable fn
        return jax.vmap(fn)
    if j is None:
        _misses.n = vmap_misses() + 1
        j = jax.jit(jax.vmap(fn))
        try:
            _VMAP_JIT[fn] = j
        except TypeError:
            pass
        return first_call("vmap", j)
    return j


# ---------------------------------------------------------------------------
# narrow ops
# ---------------------------------------------------------------------------


def map_block(b: Block, fn: Callable) -> Block:
    return Block(_vmapped(fn)(b.data), b.valid)


def map_partitions_block(b: Block, fn: Callable) -> Block:
    """fn operates on the whole block data (arrays with leading dim)."""
    out = fn(b.data)
    return Block(out, b.valid)


def filter_block(b: Block, pred: Callable) -> Block:
    keep = _vmapped(pred)(b.data)
    return Block(b.data, b.valid & keep.astype(bool))


def flatmap_block(b: Block, fn: Callable, fanout: int) -> Block:
    """fn: row → (pytree with leading dim = fanout, valid_mask[fanout])."""

    def one(row):
        out, m = fn(row)
        return out, m

    outs, masks = _vmapped(one)(b.data)  # leaves (N, F, …), masks (N, F)
    n = b.valid.shape[0]

    def flat(x):
        return x.reshape(n * fanout, *x.shape[2:])

    data = jax.tree.map(flat, outs)
    valid = (masks & b.valid[:, None]).reshape(n * fanout)
    return Block(data, valid)


def key_by_block(b: Block, fn: Callable) -> Block:
    keys = _vmapped(fn)(b.data)
    return Block({"key": keys, "value": b.data}, b.valid)


def map_values_block(b: Block, fn: Callable) -> Block:
    return Block(
        {"key": b.data["key"], "value": _vmapped(fn)(b.data["value"])}, b.valid
    )


def keys_block(b: Block) -> Block:
    return Block(b.data["key"], b.valid)


def values_block(b: Block) -> Block:
    return Block(b.data["value"], b.valid)


def sample_block(b: Block, frac: float, seed: int) -> Block:
    u = jax.random.uniform(jax.random.PRNGKey(seed + 13 * b.capacity), (b.capacity,))
    return Block(b.data, b.valid & (u < frac))


# ---------------------------------------------------------------------------
# fusable kernels: Block → Block closures over one narrow op — the unit the
# DAG planner composes into FusedStages (DESIGN.md §5). Each is jit-safe:
# fixed shapes in → fixed shapes out, no host callbacks, so a chain of them
# traces into a single XLA computation. mapPartitions is deliberately absent —
# its user fn takes raw block data and may do host-side work.
# ---------------------------------------------------------------------------


def map_kernel(fn: Callable) -> Callable:
    return lambda b: map_block(b, fn)


def filter_kernel(pred: Callable) -> Callable:
    return lambda b: filter_block(b, pred)


def flatmap_kernel(fn: Callable, fanout: int) -> Callable:
    return lambda b: flatmap_block(b, fn, fanout)


def key_by_kernel(fn: Callable) -> Callable:
    return lambda b: key_by_block(b, fn)


def map_values_kernel(fn: Callable) -> Callable:
    return lambda b: map_values_block(b, fn)


def sample_kernel(frac: float, seed: int) -> Callable:
    return lambda b: sample_block(b, frac, seed)


# ---------------------------------------------------------------------------
# reductions (log-depth pairwise fold — TPU-friendly, general binary fn)
# ---------------------------------------------------------------------------


def pairwise_reduce(data, valid, fn, identity):
    """Reduce rows with an associative jnp-vectorizable binary fn in log
    depth. ``identity`` is a row pytree substituted for masked-out rows.
    """
    n = jax.tree.leaves(data)[0].shape[0]
    m = 1
    while m < n:
        m *= 2

    def prep(x, i):
        i = jnp.asarray(i, x.dtype)
        x = jnp.where(valid.reshape((-1,) + (1,) * (x.ndim - 1)), x, i)
        if m > n:
            x = jnp.concatenate([x, jnp.broadcast_to(i, (m - n, *x.shape[1:]))], axis=0)
        return x

    data = jax.tree.map(prep, data, identity)
    k = m
    while k > 1:
        k //= 2
        lo = jax.tree.map(lambda x: x[:k], data)
        hi = jax.tree.map(lambda x: x[k : 2 * k], data)
        data = fn(lo, hi)
    return jax.tree.map(lambda x: x[0], data)


def count_block(b: Block):
    return jnp.sum(b.valid.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32))


NAMED_IDENTITIES = {
    "sum": 0,
    "max": -jnp.inf,
    "min": jnp.inf,
}

NAMED_FNS = {
    "sum": lambda a, b: jax.tree.map(jnp.add, a, b),
    "max": lambda a, b: jax.tree.map(jnp.maximum, a, b),
    "min": lambda a, b: jax.tree.map(jnp.minimum, a, b),
}
