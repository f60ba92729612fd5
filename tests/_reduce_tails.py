"""Checks of reduceByKey's kept rows (each key's total on its segment's last
row), shared by tests/test_reduce_tails.py at one executor and, run as a
script, at four (host devices; the flag must precede the jax import, so the
script runs in a subprocess and prints one ``<case>: OK|FAIL`` line each).
"""
import os
import sys

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import ICluster, IProperties, IWorker  # noqa: E402
from repro.core.partition import Block  # noqa: E402
from repro.kernels.segment_reduce import segment_reduce  # noqa: E402
from repro.kernels.segment_reduce.ref import segment_reduce_ref  # noqa: E402

#: the kernel's rows per grid step, fixed so the reference scan matches it
BLOCK = 1024
N, N_KEYS = 512, 23
TIERS = ("kernel", "oracle")
DTYPES = ("int32", "float32")
CASES = [(t, d) for t in TIERS for d in DTYPES]


def forward_scan(tier, keys, valid, vals):
    """The tier's own inclusive segmented sum over rows as the stage holds
    them: the value a segment's last row keeps, bit for bit."""
    if tier == "kernel":
        return segment_reduce(keys, valid, vals, "sum", block=BLOCK,
                              interpret=True)[1]
    return segment_reduce_ref(keys, valid, vals, "sum")[1]


def inputs(dtype, seed=5):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, N_KEYS, N).astype(np.int32)
    if dtype == "int32":
        vals = rng.integers(-1000, 1000, N).astype(np.int32)
    else:  # sums that depend on the order of association
        vals = rng.standard_normal(N).astype(np.float32)
    valid = rng.random(N) < 0.8
    return keys, vals, valid


def check_reduce_by_key(p, tier, dtype):
    """Run ``reduce_by_key(sum)`` on ``p`` executors and return a list of
    what failed (empty: all held)."""
    keys, vals, valid = inputs(dtype)
    w = IWorker(ICluster(IProperties({
        "ignis.executor.instances": str(p),
        "ignis.kernels": "interpret" if tier == "kernel" else "off",
        "ignis.kernels.blocks": str(BLOCK)})), "python")
    hits = w.metrics("kernels")["kernel_hits"]
    data = {"key": jnp.asarray(keys), "value": jnp.asarray(vals)}
    out = w.shuffle.reduce_by_key(("tails", p, tier, dtype),
                                  Block(data, jnp.asarray(valid)),
                                  lambda a, b: a + b, 0)
    fails = []
    if (w.metrics("kernels")["kernel_hits"] > hits) != (tier == "kernel"):
        fails.append("tier")
    kept = np.asarray(out.valid)
    okeys = np.asarray(out.data["key"])
    ovals = np.asarray(out.data["value"])
    rows = kept.shape[0] // p
    # one row per key, in key order, across the shards
    want_keys = np.unique(keys[valid])
    if not np.array_equal(okeys[kept], want_keys):
        fails.append("keys")
    per_key = {int(k): vals[valid & (keys == k)] for k in want_keys}
    for s in range(p):
        k, m = okeys[s * rows:(s + 1) * rows], kept[s * rows:(s + 1) * rows]
        if not m.any():
            continue
        # the shard's valid rows sort first: [0, last] holds them, and a
        # row there is kept exactly when the next row starts another key
        last = int(np.nonzero(m)[0][-1])
        tail = np.append(k[:last] != k[1:last + 1], True)
        if not (np.array_equal(m[:last + 1], tail) and not m[last + 1:].any()):
            fails.append(f"tails@{s}")
            continue
        if dtype == "float32":
            # rebuild the shard's rows: each key's values in input order
            # (every sort on the way is stable); the rest invalid
            got = {kk: iter(v) for kk, v in per_key.items()}
            sv = np.zeros(rows, np.float32)
            sv[:last + 1] = [next(got[int(x)]) for x in k[:last + 1]]
            ref = np.asarray(forward_scan(
                tier, jnp.asarray(k), jnp.asarray(np.arange(rows) <= last),
                jnp.asarray(sv)))
            seg = ovals[s * rows:(s + 1) * rows]
            if seg[m].tobytes() != ref[m].tobytes():
                fails.append(f"float_bits@{s}")
    want = np.asarray([per_key[int(k)].sum(dtype=vals.dtype) for k in want_keys])
    if dtype == "int32" and not np.array_equal(ovals[kept], want):
        fails.append("int_totals")
    if dtype == "float32" and not np.allclose(ovals[kept], want, atol=1e-4):
        fails.append("float_totals")
    return fails


def main():
    assert len(jax.devices()) == 4, jax.devices()
    for tier, dtype in CASES:
        fails = check_reduce_by_key(4, tier, dtype)
        print(f"{tier}-{dtype}: {'OK' if not fails else 'FAIL ' + ','.join(fails)}",
              flush=True)


if __name__ == "__main__":
    sys.exit(main())
