"""Inputs made on the device from ``--seed``, and the hash both the jobs'
digest apps and the host references use.

Every generator is one jitted call whose compiled program does not depend on
the seed: the seed enters as two uint32 words, so a new seed never compiles.
The Zipf generator follows ``chip_smoke.zipf_keys`` (inverse transform,
ranks shuffled), moved onto the device and made free of gathers, which are
slow on the TPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> tuple:
    """``seed`` (any whole number) as two uint32 words, high then low."""
    s = int(seed) % (1 << 64)
    return np.uint32(s >> 32), np.uint32(s & 0xFFFFFFFF)


def _key(hi, lo, stream: int):
    k = jax.random.fold_in(jax.random.key(stream), hi)
    return jax.random.fold_in(k, lo)


@partial(jax.jit, static_argnames=("n", "vocab", "s", "stream"))
def _zipf(hi, lo, *, n, vocab, s, stream):
    k1, k2 = jax.random.split(_key(hi, lo, stream))
    # inverse transform of the density x^-s on [1/2, vocab + 1/2), rounded
    # to the nearest rank: P(rank k) ~ k^-s, with no table and no gather
    a = 1.0 - s
    lo_, hi_ = 0.5 ** a, (vocab + 0.5) ** a
    u = jax.random.uniform(k1, (n,), jnp.float32)
    x = (lo_ + u * (hi_ - lo_)) ** (1.0 / a)
    rank = jnp.clip(jnp.floor(x + 0.5).astype(jnp.int32), 1, vocab) - 1
    # shuffle the ranks over the ids with an affine bijection of Z/vocab
    mul, add = jax.random.randint(k2, (2,), 0, vocab // 2, jnp.int32)
    return (rank * (2 * mul + 1) + add) & (vocab - 1)


def zipf_ids(seed: int, n: int, vocab: int, s: float, stream: int = 0):
    """``n`` int32 ids in [0, vocab) whose ranks follow Zipf's law with
    exponent ``s`` (``vocab`` a power of two, ``s`` != 1). The rank-to-id map
    is a random affine bijection, so the hot ids are not the smallest."""
    if vocab & (vocab - 1) or s == 1.0:
        raise ValueError("zipf_ids needs a power-of-two vocab and s != 1")
    hi, lo = seed_words(seed)
    return _zipf(hi, lo, n=n, vocab=vocab, s=float(s), stream=stream)


@partial(jax.jit, static_argnames=("n", "max_key", "stream"))
def _npb(hi, lo, *, n, max_key, stream):
    u = jax.random.uniform(_key(hi, lo, stream), (4, n), jnp.float32)
    k = jnp.floor(u.sum(0) * jnp.float32(max_key / 4)).astype(jnp.int32)
    return jnp.minimum(k, max_key - 1)


def npb_keys(seed: int, n: int, max_key: int, stream: int = 0):
    """NPB IS keys: floor(max_key/4 * (u1+u2+u3+u4)), int32 in [0, max_key)."""
    hi, lo = seed_words(seed)
    return _npb(hi, lo, n=n, max_key=max_key, stream=stream)


@partial(jax.jit, static_argnames=("n", "stream"))
def _uniform(hi, lo, *, n, stream):
    return jax.random.uniform(_key(hi, lo, stream), (n,), jnp.float32)


def uniform(seed: int, n: int, stream: int = 0):
    """``n`` float32 values uniform in [0, 1)."""
    hi, lo = seed_words(seed)
    return _uniform(hi, lo, n=n, stream=stream)


# ---------------------------------------------------------------------------
# digest hash: the same arithmetic on the device (jnp) and the host (numpy)
# ---------------------------------------------------------------------------

_M1, _M2, _GOLD = 0x7FEB352D, 0x846CA68B, 0x9E3779B9


def mix32(x, xp=jnp):
    """A uint32 avalanche hash; ``xp`` is ``jnp`` or ``np``."""
    x = x.astype(xp.uint32)
    x = (x ^ (x >> 16)) * xp.uint32(_M1)
    x = (x ^ (x >> 15)) * xp.uint32(_M2)
    return x ^ (x >> 16)


def pair_mix32(key, value, xp=jnp):
    """Hash of a (key, value) pair of int32 arrays."""
    v = mix32(value.astype(xp.uint32) + xp.uint32(_GOLD), xp)
    return mix32(key.astype(xp.uint32) ^ v, xp)


def bf16_round(x: np.ndarray) -> np.ndarray:
    """``x`` (float32) rounded to the nearest bfloat16, ties to even, back in
    float32: the storage precision of the controls."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32)
