"""TeraSort: sort the resident 100-byte Sort Benchmark records by their
10-byte key with ``IDataFrame.sort_by``.

On the device a record is one row of four leaves (``record_layout`` of the
configuration): ``k0`` and ``k1`` hold key bytes 0-3 and 4-7 as big-endian
uint32, ``k2`` key bytes 8-9 in the low half of a uint32, and ``payload``
(23 uint32) bytes 10-11 in the low half of its first word, then bytes 12-99
as big-endian words. The key ``(k0, k1, k2)``, compared lexicographically,
is memcmp order of the key bytes.

One job: ``sort_by`` of every record by that tuple (the sort stage), then a
native app (``void_call``) that digests the sorted records on every shard as
valsort does: the records, a wrapping sum of a hash of each whole 100-byte
record, the smallest and the largest key, and the adjacent pairs out of
memcmp order. The host adds the pairs out of order across shard boundaries.
The reference builds the same records as 100-byte strings in NumPy, orders
them with ``np.lexsort`` over the key bytes, and digests them the same way.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import gen
from benchmarks.chip.jobkit import Check, JobBase, span

WORDS = 25  # a record as big-endian uint32 words
PAYLOAD_WORDS = 23
U32_MAX = 0xFFFFFFFF
_SEED_HASH = 0x9E3779B9


# ---------------------------------------------------------------------------
# the records, made on the device from the seed
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames="n")
def _records(hi, lo, *, n):
    base = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), hi), lo)
    kk, kp = jax.random.split(base)
    key = jax.random.bits(kk, (3, n), jnp.uint32)
    low_half = jnp.full((PAYLOAD_WORDS,), U32_MAX, jnp.uint32).at[0].set(0xFFFF)
    payload = jax.random.bits(kp, (n, PAYLOAD_WORDS), jnp.uint32) & low_half
    return {"k0": key[0], "k1": key[1], "k2": key[2] >> 16, "payload": payload}


def records(seed: int, n: int) -> dict:
    """``n`` records in the layout above, their key bytes uniform random."""
    return _records(*gen.seed_words(seed), n=n)


def as_bytes(rec: dict) -> np.ndarray:
    """Host records in the layout above as (n, 100) uint8, as gensort
    writes them."""
    n = len(rec["k0"])

    def be(x):  # a fetched (n, 23) array may come in column order
        return np.ascontiguousarray(x, ">u4").view(np.uint8).reshape(n, -1)

    out = np.empty((n, 4 * WORDS), np.uint8)
    out[:, 0:4] = be(rec["k0"])
    out[:, 4:8] = be(rec["k1"])
    out[:, 8:10] = be(rec["k2"])[:, 2:]
    out[:, 10:] = be(rec["payload"])[:, 2:]
    return out


# ---------------------------------------------------------------------------
# the digest: the same arithmetic on the device (jnp) and the host (numpy)
# ---------------------------------------------------------------------------


def record_hash(words, xp=jnp):
    """A uint32 hash of each record from its 25 big-endian words, in order."""
    h = xp.full(words[0].shape, _SEED_HASH, xp.uint32)
    for w in words:
        h = gen.mix32(h ^ w, xp)
    return h


def _device_words(k0, k1, k2, payload):
    return [k0, k1, (k2 << 16) | payload[:, 0],
            *(payload[:, j] for j in range(1, PAYLOAD_WORDS))]


def _lex_extreme(keys, valid, largest: bool):
    """The smallest (or largest) key tuple among the valid rows."""
    m, out = valid, []
    for k in keys:
        e = jnp.where(m, k, jnp.uint32(0 if largest else U32_MAX))
        e = e.max() if largest else e.min()
        out.append(e)
        m = m & (k == e)
    return out


def _lex_less(a, b):
    """a < b, lexicographically over tuples of equally shaped arrays."""
    lt, eq = False, True
    for x, y in zip(a, b):
        lt = lt | (eq & (x < y))
        eq = eq & (x == y)
    return lt


def _local_digest(k0, k1, k2, payload, valid):
    u = jnp.uint32
    keys = (k0, k1, k2)
    # invalid rows compare as the largest key, so one in the middle shows
    m = [jnp.where(valid, k, jnp.uint32(U32_MAX)) for k in keys]
    breaks = _lex_less([x[1:] for x in m], [x[:-1] for x in m]).sum(dtype=u)
    h = jnp.where(valid, record_hash(_device_words(k0, k1, k2, payload)), 0)
    return jnp.stack([valid.sum(dtype=u), h.sum(dtype=u),
                      *_lex_extreme(keys, valid, False),
                      *_lex_extreme(keys, valid, True), breaks])


def combine(per_shard: np.ndarray) -> tuple:
    """Shard digests (executors, 9) in shard order -> (records, hash sum,
    smallest key, largest key, pairs out of order)."""
    d = np.asarray(per_shard, np.uint32).astype(np.int64)
    full = d[d[:, 0] > 0]
    lo = [tuple(map(int, r)) for r in full[:, 2:5]]
    hi = [tuple(map(int, r)) for r in full[:, 5:8]]
    breaks = int(d[:, 8].sum()) + sum(b < a for a, b in zip(hi[:-1], lo[1:]))
    return (int(d[:, 0].sum()), int(d[:, 1].sum() % 2**32),
            min(lo) if lo else (), max(hi) if hi else (), breaks)


def _key_ints(rec_bytes: np.ndarray) -> tuple:
    """The key bytes of (n, 100) records as big-endian (uint64 of bytes 0-7,
    uint16 of bytes 8-9): memcmp order is their lexicographic order."""
    hi = np.ascontiguousarray(rec_bytes[:, :8]).view(">u8").ravel().astype(np.uint64)
    lo = np.ascontiguousarray(rec_bytes[:, 8:10]).view(">u2").ravel().astype(np.uint16)
    return hi, lo


def digest(rec_bytes: np.ndarray, order: np.ndarray) -> tuple:
    """The digest of (n, 100) records read in ``order``."""
    words = rec_bytes.view(">u4")
    h = record_hash([words[:, j].astype(np.uint32) for j in range(WORDS)], np)
    hi, lo = _key_ints(rec_bytes)
    hi_o, lo_o = hi[order], lo[order]
    breaks = int(((hi_o[1:] < hi_o[:-1])
                  | ((hi_o[1:] == hi_o[:-1]) & (lo_o[1:] < lo_o[:-1]))).sum())

    def key(pick):  # the smallest or largest key, as (k0, k1, k2)
        first = pick(hi)
        return (int(first >> np.uint64(32)), int(first & np.uint64(U32_MAX)),
                int(pick(lo[hi == first])))

    return (len(rec_bytes), int(h.sum(dtype=np.uint32)), key(np.min), key(np.max),
            breaks)


def memcmp_order(rec_bytes: np.ndarray) -> np.ndarray:
    """The order of the records by their 10 key bytes, as unsigned bytes."""
    return np.lexsort(rec_bytes[:, 9::-1].T)


def _sort_key(r):
    return (r["k0"], r["k1"], r["k2"])


class Job(JobBase):
    def setup(self):
        c = self.cfg
        if (int(c["record_bytes"]), int(c["key_bytes"])) != (4 * WORDS, 10):
            raise ValueError("the job lays out 100-byte records with 10-byte keys")
        self.records = int(c["records"])
        # the sort stage's least bytes: each record read and written once
        self.kernel_shapes = {"sort_stage": {"records": self.records,
                                             "record_bytes": int(c["record_bytes"])}}
        self.df = self.worker("dataflow")
        # through the host, so the generator's copy is gone before the
        # worker's is made
        self.src = self.df.parallelize(jax.device_get(self.make()))

    def make(self) -> dict:
        return records(self.seed, int(self.cfg["records"]))

    def _app(self, ctx, data=None, valid=None):
        return self.digest(ctx, _local_digest, data["k0"], data["k1"], data["k2"],
                           data["payload"], valid)

    def run_one(self):
        with span("build"):
            ranked = self.src.sort_by(_sort_key)
        with span("submit"):
            fut = self.df.void_call_async(self._app, ranked)
        with span("wait"):
            return combine(jax.device_get(fut.result()))

    def host_records(self) -> np.ndarray:
        return as_bytes(jax.device_get(self.make()))

    def control_answer(self):
        """The reference with keys compared on their first 4 bytes only."""
        rec = self.host_records()
        first4 = np.ascontiguousarray(rec[:, :4]).view(">u4").ravel()
        return digest(rec, np.argsort(first4, kind="stable"))

    def check(self, answers):
        rec = self.host_records()
        ref = digest(rec, memcmp_order(rec))
        wrong = [a for a in answers if a != ref]
        if wrong:
            self.detail = f"first wrong answer {wrong[0]} != reference {ref}"
        return [Check("wrong_answers", len(wrong), 0)]
