"""NPB IS: sort the resident keys with ``IDataFrame.sort()``.

One job: ``sort()`` of every key (the sort stage; on several executors the
PSRS sampling, pivots and ``all_to_all`` exchange), then a native app
(``void_call``) that verifies the sorted frame on every shard, as NPB's
partial verification does each iteration: valid keys, the wrapping sums of
the keys and of a hash of each key, the smallest and largest key, and the
adjacent pairs out of order. The host then adds the pairs out of order across
shard boundaries. The reference digests the same keys in NumPy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import gen
from benchmarks.chip.jobkit import Check, JobBase, span

I32_MAX = np.iinfo(np.int32).max


def _local_digest(k, valid):
    u = jnp.uint32
    m = jnp.where(valid, k, I32_MAX)
    return jnp.stack([
        valid.sum(dtype=u),
        jnp.where(valid, k, 0).astype(u).sum(dtype=u),
        jnp.where(valid, gen.mix32(k), 0).sum(dtype=u),
        m.min().astype(u),
        jnp.where(valid, k, 0).max().astype(u),
        (m[1:] < m[:-1]).sum(dtype=u),
    ])


def combine(per_shard: np.ndarray) -> tuple:
    """Shard digests (executors, 6) in shard order -> (keys, sum, hash, min,
    max, pairs out of order)."""
    d = np.asarray(per_shard, np.uint32).astype(np.int64)
    full = d[d[:, 0] > 0]
    breaks = int(d[:, 5].sum()) + int((full[1:, 3] < full[:-1, 4]).sum())
    return (int(d[:, 0].sum()), int(d[:, 1].sum() % 2**32),
            int(d[:, 2].sum() % 2**32), int(full[:, 3].min()) if len(full) else -1,
            int(full[:, 4].max()) if len(full) else -1, breaks)


def digest(keys: np.ndarray, ordered: bool = True) -> tuple:
    """The same digest of a host array of keys in the order given, or, with
    ``ordered=False``, of the same keys sorted (no pair out of order)."""
    k = keys.astype(np.int64)
    breaks = int((k[1:] < k[:-1]).sum()) if ordered else 0
    return (len(keys), int(keys.astype(np.uint32).sum(dtype=np.uint32)),
            int(gen.mix32(keys, np).sum(dtype=np.uint32)), int(k.min()),
            int(k.max()), breaks)


class Job(JobBase):
    def setup(self):
        self.records = int(self.cfg["keys"])
        self.df = self.worker("dataflow")
        self.src = self.df.parallelize(self.keys())

    def keys(self):
        c = self.cfg
        return gen.npb_keys(self.seed, int(c["keys"]), int(c["max_key"]))

    def _app(self, ctx, data=None, valid=None):
        return self.digest(ctx, _local_digest, data, valid)

    def run_one(self):
        with span("build"):
            ranked = self.src.sort()
        with span("submit"):
            fut = self.df.void_call_async(self._app, ranked)
        with span("wait"):
            return combine(jax.device_get(fut.result()))

    def host_keys(self) -> np.ndarray:
        return np.asarray(jax.device_get(self.keys()))

    def control_answer(self):
        """The reference sort with its keys compared as bfloat16."""
        k = self.host_keys()
        bits = gen.bf16_round(k.astype(np.float32)).view(np.uint32) >> 16
        return digest(k[np.argsort(bits.astype(np.uint16), kind="stable")])

    def check(self, answers):
        ref = digest(self.host_keys(), ordered=False)
        wrong = [a for a in answers if a != ref]
        if wrong:
            self.detail = f"first wrong answer {wrong[0]} != reference {ref}"
        return [Check("wrong_answers", len(wrong), 0)]
