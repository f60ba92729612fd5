"""Faults planted in the program under the timed path, for the tests that
see ``correct`` come out false. Each is a context manager that patches the
shuffle engine while a run builds and runs its jobs."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


@contextlib.contextmanager
def _patched(name: str, make):
    from repro.core import shuffle

    orig = getattr(shuffle, name)
    setattr(shuffle, name, make(orig))
    try:
        yield
    finally:
        setattr(shuffle, name, orig)


def half_batch():
    """Every wide sort stage sees only the first half of its rows."""

    def make(orig):
        def stage(ctx, keys, valid, data, C, post=None):
            keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
            return orig(ctx, keys, valid & keep, data, C, post)

        return stage

    return _patched("sort_stage", make)


def altered_answer():
    """Every wide sort stage adds 1 to each leaf of its first output row."""

    def make(orig):
        def stage(*args, **kw):
            (data, valid), overflow, fill = orig(*args, **kw)
            data = jax.tree.map(lambda x: x.at[0].add(jnp.ones((), x.dtype)), data)
            return (data, valid), overflow, fill

        return stage

    return _patched("sort_stage", make)


def no_exchange():
    """The all_to_all between executors is left out: every shard keeps the
    buckets it packed for the others."""
    return _patched("_exchange", lambda orig: lambda packed, axis, p, C: packed)


FAULTS = {"half_batch": half_batch, "altered_answer": altered_answer,
          "no_exchange": no_exchange}
