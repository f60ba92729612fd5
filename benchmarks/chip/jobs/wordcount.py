"""WordCount over dictionary-encoded tokens.

One job: ``map`` each resident token to ``{key: token, value: 1}``,
``reduce_by_key(sum)`` (sort stage, segment heads and the ``segment_reduce``
kernel in one wide stage), then a native app (``void_call``) that digests the
word table on every shard: distinct words, tokens, the wrapping sum of a hash
of every (word, count) pair, and the largest count. The reference counts the
same tokens with ``np.bincount`` and digests its table the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import gen
from benchmarks.chip.jobkit import Check, JobBase, span


def _to_pair(t):
    return {"key": t, "value": jnp.ones_like(t)}


def _add(a, b):
    return a + b


def _local_digest(key, value, valid):
    u = jnp.uint32
    return jnp.stack([
        valid.sum(dtype=u),
        jnp.where(valid, value, 0).astype(u).sum(dtype=u),
        jnp.where(valid, gen.pair_mix32(key, value), 0).sum(dtype=u),
        jnp.where(valid, value, 0).max().astype(u),
    ])


def combine(per_shard: np.ndarray) -> tuple:
    """Shard digests (executors, 4) -> one (words, tokens, hash, max)."""
    d = np.asarray(per_shard, np.uint32)
    return (int(d[:, 0].sum()), int(d[:, 1].astype(np.int64).sum()),
            int(d[:, 2].sum(dtype=np.uint32)), int(d[:, 3].max()))


def table_digest(counts: np.ndarray) -> tuple:
    """The same digest of a dense word table (count per word id)."""
    ids = np.nonzero(counts)[0].astype(np.int32)
    c = counts[ids].astype(np.int32)
    h = gen.pair_mix32(ids, c, np).sum(dtype=np.uint32)
    return (len(ids), int(counts.sum()), int(h), int(counts.max()))


class Job(JobBase):
    def setup(self):
        c = self.cfg
        self.records = int(c["tokens"])
        # the kernel scans each shard's rows; on several executors it scans
        # the capacity-padded receive buffer, so this counts the least bytes
        self.kernel_shapes = {"segment_reduce": {
            "rows": self.records // self.executors, "cols": 1, "itemsize": 4}}
        self.df = self.worker("dataflow")
        self.src = self.df.parallelize(self.tokens())

    def tokens(self):
        c = self.cfg
        return gen.zipf_ids(self.seed, int(c["tokens"]), int(c["vocab"]),
                            float(c["zipf_s"]))

    def _app(self, ctx, data=None, valid=None):
        return self.digest(ctx, _local_digest, data["key"], data["value"], valid)

    def run_one(self):
        with span("build"):
            table = self.src.map(_to_pair).reduce_by_key(_add, 0)
        with span("submit"):
            fut = self.df.void_call_async(self._app, table)
        with span("wait"):
            return combine(jax.device_get(fut.result()))

    def reference_counts(self) -> np.ndarray:
        return np.bincount(np.asarray(jax.device_get(self.tokens())),
                           minlength=int(self.cfg["vocab"]))

    def control_answer(self):
        """The reference with its counts kept in bfloat16."""
        counts = self.reference_counts()
        return table_digest(gen.bf16_round(counts.astype(np.float32)).astype(np.int64))

    def check(self, answers):
        ref = table_digest(self.reference_counts())
        wrong = [a for a in answers if a != ref]
        if wrong:
            self.detail = f"first wrong answer {wrong[0]} != reference {ref}"
        return [Check("wrong_answers", len(wrong), 0)]
