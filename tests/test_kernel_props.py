"""Property-based kernel differential tests (hypothesis): random shapes,
ops and dtypes against the pure-jnp oracles, plus the end-to-end
reduceByKey path with the kernel tier forced on vs a Python oracle
(docs/kernels.md — bit-identity is the contract for associative-exact
data, so every comparison here is exact, never a tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
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


def _gather_fill(dest, payload, end, p, C):
    """The PSRS send buckets filled by a gather over the p·C slots, as the
    sort stage filled them before it took slices."""
    bounds = jnp.searchsorted(dest, jnp.arange(p + 1, dtype=dest.dtype), side="left")
    starts = bounds[:p].astype(jnp.int32)
    counts = jnp.maximum(jnp.minimum(bounds[1:].astype(jnp.int32), end) - starts, 0)
    slot = jnp.arange(p * C, dtype=jnp.int32)
    d, i = slot // C, slot % C
    keep = i < counts[d]
    src = jnp.where(keep, starts[d] + i, 0)

    def pack(x):
        m = keep.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x[src], jnp.zeros((), x.dtype))

    return (jax.tree.map(pack, payload), keep,
            jnp.maximum(counts - C, 0).sum().astype(jnp.int32),
            counts.max().astype(jnp.int32))


@given(st.integers(2, 8), st.integers(1, 64),
       st.lists(st.integers(0, 7), min_size=1, max_size=300),
       st.integers(0, 40), st.integers(0, 2**32 - 1))
@_settings
def test_fill_sorted_random(p, capacity, dest, tail, seed):
    # the PSRS fill by slices vs the gather it replaced, on sorted
    # destinations with an invalid tail: every slot bit for bit (a masked
    # slot is zero, even where the leaf holds -0.0 or NaN bits), and the
    # overflow and largest bucket demand equal
    d = np.sort(np.asarray([x % p for x in dest], np.int32))
    end = d.shape[0]
    d = np.concatenate([d, np.full(tail, p, np.int32)])
    n = d.shape[0]
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 2**32, (n, 2), dtype=np.uint64).astype(np.uint32)
    rows = {"f": jnp.asarray(bits.view(np.float32)),
            "row": jnp.arange(n, dtype=jnp.int32),
            "valid": jnp.asarray(np.arange(n) < end)}
    args = (jnp.asarray(d), rows, jnp.int32(end), p, capacity)
    got, overflow, max_fill = sh._fill_sorted(*args)
    want, keep, w_overflow, w_max_fill = _gather_fill(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bits_equal(g, w)
    masked = np.asarray(got["f"]).view(np.uint32)[~np.asarray(keep)]
    assert not masked.any()
    assert int(overflow) == int(w_overflow) and int(max_fill) == int(w_max_fill)


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
