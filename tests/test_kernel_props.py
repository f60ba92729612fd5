"""Property-based kernel differential tests (hypothesis): random shapes,
ops and dtypes against the pure-jnp oracles, plus the end-to-end
reduceByKey path with the kernel tier forced on vs a Python oracle
(docs/kernels.md — bit-identity is the contract for associative-exact
data, so every comparison here is exact, never a tolerance)."""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis", reason="dev-only dependency (requirements-dev.txt)")
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core import ICluster, IProperties, IWorker
from repro.core import shuffle as sh
from repro.core.shuffle import segmented_reduce
from repro.kernels.segment_reduce import segment_reduce, segment_totals

_settings = settings(max_examples=12, deadline=None,
                     suppress_health_check=list(HealthCheck))

_FNS = {"sum": lambda a, b: a + b, "max": jnp.maximum, "min": jnp.minimum}
_CUM = {"sum": np.cumsum, "max": np.maximum.accumulate,
        "min": np.minimum.accumulate}
ops = st.sampled_from(["sum", "max", "min"])
ints = st.lists(st.integers(-1000, 1000), min_size=1, max_size=300)


def bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


_worker = None


def worker():
    global _worker
    if _worker is None:
        _worker = IWorker(ICluster(IProperties({"ignis.kernels": "interpret"})),
                          "python")
    return _worker


@given(ints, ops, st.sampled_from([7, 64, 256, 2048]))
@_settings
def test_single_segment_scan_random(xs, op, block):
    # one segment (every key equal, every row valid) is a prefix scan
    x = np.asarray(xs, np.int32)
    n = x.shape[0]
    _, got = segment_reduce(jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
                            jnp.asarray(x), op=op, block=block, interpret=True)
    assert bits_equal(got, _CUM[op](x).astype(np.int32))


@given(st.lists(st.tuples(st.integers(0, 9), st.integers(-100, 100),
                          st.booleans()),
                min_size=1, max_size=200),
       ops, st.sampled_from(["int32", "float32"]))
@_settings
def test_segment_totals_random(rows, op, dtype):
    rows = sorted(rows)  # segmented_reduce requires sorted keys
    keys = jnp.asarray([k for k, _, _ in rows], jnp.int32)
    vals = jnp.asarray(np.asarray([v for _, v, _ in rows], dtype))
    valid = jnp.asarray([m for _, _, m in rows])
    ident = jnp.asarray({"sum": 0, "max": -(2**31 - 1),
                         "min": 2**31 - 1}[op], dtype)
    h1, t1 = segment_totals(keys, valid, vals, op, ident, block=64,
                            interpret=True)
    h2, t2 = segmented_reduce(keys, valid, vals, _FNS[op], ident)
    assert bits_equal(h1, h2) and bits_equal(t1, t2)


@given(st.integers(1, 8), st.integers(1, 64),
       st.lists(st.integers(0, 7), min_size=1, max_size=300))
@_settings
def test_pack_random(p, capacity, dest):
    # the hash exchange's pack step vs a stable sort by destination: each
    # kept row in its bucket's slot, in row order, and the overflow and
    # largest bucket demand counted
    d = np.asarray([x % p for x in dest], np.int32)
    n = d.shape[0]
    rows = {"row": jnp.arange(n, dtype=jnp.int32), "valid": jnp.ones(n, bool)}
    packed, overflow, max_fill = sh._pack(jnp.asarray(d), rows, p, capacity)
    counts = np.bincount(d, minlength=p)
    want = np.full(p * capacity, -1)
    for b in range(p):
        mine = np.nonzero(d == b)[0][:capacity]
        want[b * capacity: b * capacity + mine.size] = mine
    got = np.where(np.asarray(packed["valid"]), np.asarray(packed["row"]), -1)
    assert np.array_equal(got, want)
    assert int(overflow) == n - int(np.minimum(counts, capacity).sum())
    assert int(max_fill) == int(counts.max())


@given(st.lists(st.integers(0, 2**15 - 1), min_size=1, max_size=60),
       st.integers(1, 7), ops)
@_settings
def test_reduce_by_key_kernel_tier_matches_python(xs, k, op):
    df = (worker().parallelize(np.asarray(xs, np.int32))
          .map(lambda x: {"key": x % k, "value": x})
          .reduce_by_key(_FNS[op], {"sum": 0, "max": 0, "min": 2**31 - 1}[op]))
    got = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
           for r in df.collect()}
    red = {"sum": lambda a, b: a + b, "max": max, "min": min}[op]
    exp = {}
    for x in xs:
        exp[x % k] = red(exp[x % k], x) if x % k in exp else x
    assert got == exp
    assert worker().shuffle_stats()["kernel_hits"] >= 1
