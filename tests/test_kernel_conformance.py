"""Kernel conformance + differential tier (docs/kernels.md, DESIGN.md §11).

Three layers, each against an always-available oracle:

  * **kernel conformance** — the shuffle-tier kernel (segment_reduce and
    its stage entry segment_totals) × op (sum/max/min) × dtype
    (f32/i32/bool) × edge shape (ragged / empty / single-segment /
    all-invalid) is BIT-identical to the lax cumulative primitives and the
    core/shuffle oracle in interpret mode, and the hash exchange's pack
    step matches a NumPy stable-argsort reference. f32 sums use
    integer-valued data (< 2^24) so the association order cannot show:
    bit-identity is the contract, not a tolerance.
  * **registry semantics** — mode resolution, capability-probe failure
    degrading to the fallback, builtin-op recognition, and the autotune
    memo's LRU + single-builder discipline (comm.py plan-cache pattern).
  * **wide-stage equivalence** — every shuffle kind run with the kernel
    tier forced ON (interpret) and OFF must produce identical collected
    rows AND identical overflow-retry counters, with the kernel actually
    engaged (kernel_hits > 0) on the eligible kinds. The p=8 twin of
    this block lives in tests/_distributed_main.py.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICluster, IProperties, IWorker
from repro.core import faults
from repro.core import shuffle as sh
from repro.core.faults import FaultPlan
from repro.core.shuffle import segmented_reduce
from repro.kernels import registry as reg
from repro.kernels.registry import KernelRegistry, builtin_reduce_op
from repro.kernels.segment_reduce import segment_reduce, segment_totals

KEY = jax.random.PRNGKey(11)

OPS = ("sum", "max", "min")
_FNS = {"sum": lambda a, b: a + b, "max": jnp.maximum, "min": jnp.minimum}
_IDENT = {"sum": 0, "max": -(2**31 - 1), "min": 2**31 - 1}


def bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b)


def _data(n, dtype, seed=0):
    """Integer-valued samples: every op is associative-exact, so kernel
    vs oracle must agree to the bit even for float32."""
    r = np.random.default_rng(seed).integers(-1000, 1000, n)
    if dtype == "bool":
        return jnp.asarray(r % 2 == 0)
    return jnp.asarray(r.astype(dtype))


# ---------------------------------------------------------------------------
# segment_reduce over one segment — a prefix scan: op × dtype × size
# (ragged/empty/single)
# ---------------------------------------------------------------------------

_CUM = {"sum": jnp.cumsum, "max": jax.lax.cummax, "min": jax.lax.cummin}


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("dtype", ["float32", "int32", "bool"])
@pytest.mark.parametrize("n", [0, 1, 5, 64, 200, 513])
def test_single_segment_scan_matches_cumulative(op, dtype, n):
    # every key equal, every row valid: the segmented scan is the inclusive
    # prefix scan, in the compute dtype (bool rides as i32)
    x = _data(n, dtype, seed=n)
    _, got = segment_reduce(jnp.zeros(n, jnp.int32), jnp.ones(n, bool), x,
                            op=op, block=64, interpret=True)
    assert bits_equal(got, _CUM[op](x.astype(jnp.int32) if dtype == "bool" else x))


# ---------------------------------------------------------------------------
# segment_totals — the reduceByKey stage ABI vs core/shuffle.segmented_reduce
# ---------------------------------------------------------------------------


def _segments(n, n_keys, valid_frac, dtype, d=None, seed=3):
    ks = np.random.default_rng(seed)
    keys = jnp.sort(jnp.asarray(ks.integers(0, n_keys, n).astype(np.int32)))
    valid = jnp.asarray(ks.random(n) < valid_frac)
    shape = n if d is None else (n, d)
    vals = jnp.asarray(ks.integers(-50, 50, shape).astype(dtype))
    return keys, valid, vals


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("n,n_keys,valid_frac,d", [
    (256, 17, 0.8, None),     # ragged runs, scattered invalids
    (300, 17, 0.8, 4),        # non-multiple of block, row values
    (200, 1, 1.0, None),      # single segment spanning blocks
    (64, 40, 0.0, None),      # all-invalid: every row its own boundary
    (1, 1, 1.0, None),        # single row
])
def test_segment_totals_matches_oracle(op, dtype, n, n_keys, valid_frac, d):
    keys, valid, vals = _segments(n, n_keys, valid_frac, dtype, d)
    ident = jnp.asarray(_IDENT[op], dtype)
    h1, t1 = segment_totals(keys, valid, vals, op, ident, block=64,
                            interpret=True)
    h2, t2 = segmented_reduce(keys, valid, vals, _FNS[op], ident)
    assert bits_equal(h1, h2)
    assert bits_equal(t1, t2)


def test_segment_totals_empty_input():
    z = jnp.zeros(0, jnp.int32)
    h, t = segment_totals(z, jnp.zeros(0, bool), z, "sum", jnp.int32(0),
                          interpret=True)
    assert h.shape == (0,) and t.shape == (0,)


@pytest.mark.parametrize("op", ["max", "min"])
def test_segment_totals_bool_values(op):
    # bool rides as i32; max/min are OR/AND — exact either way
    keys, valid, _ = _segments(128, 9, 0.9, "int32")
    vals = _data(128, "bool", seed=5)
    ident = jnp.asarray(op == "min", bool)
    h1, t1 = segment_totals(keys, valid, vals, op, ident, block=32,
                            interpret=True)
    h2, t2 = segmented_reduce(keys, valid, vals, _FNS[op], ident)
    assert bits_equal(h1, h2) and bits_equal(t1, t2)


def test_segment_totals_nonzero_identity_at_invalid_rows():
    # the user identity never enters a combine, but it IS the output at
    # invalid rows (they are their own segments) — the oracle's contract
    keys, valid, vals = _segments(96, 7, 0.5, "int32", seed=9)
    ident = jnp.int32(41)
    _, t1 = segment_totals(keys, valid, vals, "sum", ident, block=32,
                           interpret=True)
    _, t2 = segmented_reduce(keys, valid, vals, _FNS["sum"], ident)
    assert bits_equal(t1, t2)
    assert bool((t1[~valid] == 41).all())


def test_segment_totals_block_size_is_invisible():
    # 3000 rows: the smaller blocks take several grid steps, so the carry
    # across row tiles is exercised, and every block gives the same bits
    keys, valid, vals = _segments(3000, 40, 0.9, "int32", seed=4)
    ref = segmented_reduce(keys, valid, vals, _FNS["sum"], jnp.int32(0))
    for block in (1, 7, 1024, 2048, 4096):
        got = segment_totals(keys, valid, vals, "sum", jnp.int32(0),
                             block=block, interpret=True)
        assert all(bits_equal(g, r) for g, r in zip(got, ref))


@pytest.mark.parametrize("n", [16, 17, 100])
def test_chunked_scan_matches_associative_scan(n):
    # the oracle scans long inputs chunk by chunk (XLA:TPU compiles one
    # chunk-sized program); the carry across chunks must be exact
    x = _data(n, "int32", seed=n)
    assert bits_equal(sh.chunked_scan(jnp.minimum, x, chunk=16), jax.lax.cummin(x))

    def comb(a, b):
        return jnp.where(b[1][:, None], b[0], a[0] + b[0]), a[1] | b[1]

    v = jnp.stack([x, -x, 2 * x], axis=1)
    f = _data(n, "bool", seed=n + 1)
    got = sh.chunked_scan(comb, (v, f), chunk=16)
    ref = jax.lax.associative_scan(comb, (v, f))
    assert all(bits_equal(a, b) for a, b in zip(got, ref))


def test_segment_totals_matches_oracle_across_scan_chunks():
    keys, valid, vals = _segments(sh.SCAN_CHUNK + 300, 500, 0.9, "int32")
    h1, t1 = segment_totals(keys, valid, vals, "sum", jnp.int32(0), interpret=True)
    h2, t2 = segmented_reduce(keys, valid, vals, _FNS["sum"], jnp.int32(0))
    assert bits_equal(h1, h2) and bits_equal(t1, t2)


# ---------------------------------------------------------------------------
# hash exchange pack step — bucket slots vs a NumPy stable-argsort reference
# ---------------------------------------------------------------------------


def _pack_ref(dest, p, capacity):
    """Each kept row's slot in the (p·C) buffer, overflow and max_fill: a
    stable sort by destination ranks each row within its bucket."""
    n = dest.shape[0]
    order = np.argsort(dest, kind="stable")
    counts = np.bincount(dest, minlength=p)
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n) - (np.cumsum(counts) - counts)[dest[order]]
    keep = pos < capacity
    slots = np.full(p * capacity, -1)
    slots[(dest * capacity + pos)[keep]] = np.nonzero(keep)[0]
    return slots, n - int(keep.sum()), int(counts.max())


def _check_pack(dest, p, capacity):
    n = dest.shape[0]
    rows = {"row": jnp.arange(n, dtype=jnp.int32), "valid": jnp.ones(n, bool)}
    packed, overflow, max_fill = sh._pack(jnp.asarray(dest), rows, p, capacity)
    slots, ovf, fill = _pack_ref(dest, p, capacity)
    got = np.where(np.asarray(packed["valid"]), np.asarray(packed["row"]), -1)
    assert np.array_equal(got, slots)
    assert int(overflow) == ovf and int(max_fill) == fill


@pytest.mark.parametrize("n,p,capacity", [
    (0, 4, 2),          # empty
    (1, 2, 1),          # single row
    (100, 8, 20),       # roomy
    (100, 8, 5),        # tight: overflow rows dropped
    (600, 2, 400),      # large buckets
    (257, 5, 1),        # capacity 1, ragged tail
])
def test_pack_matches_stable_argsort(n, p, capacity):
    rng = np.random.default_rng(n + p)
    _check_pack(rng.integers(0, p, n).astype(np.int32), p, capacity)


def _fill_leaf(kind, n, rng):
    if kind == "int32":
        return jnp.asarray(rng.integers(-2**31, 2**31, n), jnp.int32)
    if kind == "uint32x23":
        return jnp.asarray(rng.integers(0, 2**32, (n, 23)), jnp.uint32)
    assert kind == "bool"
    return jnp.asarray(rng.random(n) < 0.5)


@pytest.mark.parametrize("counts,tail,capacity,kind", [
    ((30, 30, 30, 10), 0, 40, "int32"),    # bucket 3 starts at 90 > n - C = 60
    ((5, 9, 3, 7), 24, 12, "int32"),       # invalid tail: end < n
    ((20, 3, 17), 4, 8, "int32"),          # overflow: counts > C
    ((6, 0, 10), 0, 16, "int32"),          # C = n
    ((0, 7, 0, 5, 0), 3, 6, "int32"),      # empty buckets, first and last
    ((9, 4, 11, 6), 5, 10, "uint32x23"),   # a leaf with trailing dimensions
    ((9, 4, 11, 6), 5, 10, "bool"),        # a bool leaf
])
def test_fill_sorted_matches_stable_argsort(counts, tail, capacity, kind):
    # the PSRS send buckets filled by slices of destination-sorted rows vs
    # the hash exchange's reference: each kept row in its slot, zero in
    # every other, and the rows from ``end`` on neither sent nor counted
    p, end = len(counts), sum(counts)
    dest = np.repeat(np.arange(p + 1, dtype=np.int32), [*counts, tail])
    n = dest.shape[0]
    rng = np.random.default_rng(n + p)
    x = _fill_leaf(kind, n, rng)
    rows = {"row": jnp.arange(n, dtype=jnp.int32), "x": x}
    packed, overflow, max_fill = sh._fill_sorted(
        jnp.asarray(dest), rows, jnp.int32(end), p, capacity)
    slots, ovf, fill = _pack_ref(dest[:end], p, capacity)
    kept = (slots >= 0).reshape((-1,) + (1,) * (x.ndim - 1))
    want = np.where(kept, np.asarray(x)[np.maximum(slots, 0)], np.zeros((), x.dtype))
    assert bits_equal(packed["x"], want)
    assert bits_equal(packed["row"], np.where(slots >= 0, slots, 0).astype(np.int32))
    assert int(overflow) == ovf and int(max_fill) == fill


def test_pack_all_one_destination():
    dest = np.zeros(90, np.int32)
    _check_pack(dest, 4, 100)
    packed, overflow, max_fill = sh._pack(
        jnp.asarray(dest), {"row": jnp.arange(90, dtype=jnp.int32)}, 4, 100)
    assert bits_equal(packed["row"][:90], jnp.arange(90, dtype=jnp.int32))
    assert int(overflow) == 0 and int(max_fill) == 90


# ---------------------------------------------------------------------------
# registry: mode resolution + capability fallback
# ---------------------------------------------------------------------------


def test_registry_rejects_unknown_mode():
    with pytest.raises(ValueError, match="ignis.kernels"):
        KernelRegistry(mode="sometimes")


def test_mode_off_always_falls_back():
    r = KernelRegistry(mode="off")
    assert r.select() is None
    assert r.stats == {"kernel_hits": 0, "kernel_fallbacks": 1,
                       "autotune_runs": 0, "autotune_evictions": 0}


def test_mode_auto_never_interprets_off_tpu():
    r = KernelRegistry(mode="auto")
    sel = r.select()
    if reg.compiled_backend():
        assert sel is not None and not sel.interpret
    else:  # interpreted Pallas is strictly slower than the jnp oracle
        assert sel is None and r.stats["kernel_fallbacks"] == 1


def test_mode_interpret_selects_interpreted_kernel():
    r = KernelRegistry(mode="interpret")
    sel = r.select()
    assert sel is not None and sel.interpret
    assert sel.describe() == "segment_reduce[interpret]"
    assert r.stats["kernel_hits"] == 1


def test_mode_on_uses_interpret_where_not_compiled():
    r = KernelRegistry(mode="on")
    sel = r.select()
    assert sel is not None
    assert sel.interpret == (not reg.compiled_backend())


def test_probe_failure_degrades_to_fallback(monkeypatch):
    def boom(interpret):
        raise RuntimeError("no such kernel on this backend")

    monkeypatch.setattr(reg, "_probe_segment_reduce", boom)
    r = KernelRegistry(mode="interpret")
    assert r.select() is None
    assert r.stats["kernel_fallbacks"] == 1
    # the probe result is cached: a second select does not re-probe
    monkeypatch.setattr(reg, "_probe_segment_reduce", lambda interpret: None)
    assert r.select() is None


def test_capability_fault_degrades_without_error():
    r = KernelRegistry(mode="interpret")
    plan = FaultPlan().fail_kernel_capability("segment_reduce", times=1)
    with faults.inject(plan):
        assert r.select() is None      # degraded
        assert r.select() is not None  # times=1: recovered
    assert r.stats["kernel_fallbacks"] == 1 and r.stats["kernel_hits"] == 1


def test_demote_rebooks_hit_as_fallback():
    r = KernelRegistry(mode="interpret")
    assert r.select() is not None
    r.demote()
    assert r.stats == {"kernel_hits": 0, "kernel_fallbacks": 1,
                       "autotune_runs": 0, "autotune_evictions": 0}


# ---------------------------------------------------------------------------
# registry: builtin-op recognition (what reduceByKey may hand the kernel)
# ---------------------------------------------------------------------------


def test_builtin_reduce_op_recognizes_builtins():
    v, i = jnp.zeros(4, jnp.int32), jnp.int32(0)
    assert builtin_reduce_op(lambda a, b: a + b, i, v) == "sum"
    assert builtin_reduce_op(jnp.maximum, i, v) == "max"
    assert builtin_reduce_op(jnp.minimum, i, v) == "min"
    assert builtin_reduce_op(lambda a, b: a + b, jnp.float32(0),
                             jnp.zeros((4, 2), jnp.float32)) == "sum"


@pytest.mark.parametrize("fn", [
    lambda a, b: a + b + 1,     # extra eqn
    lambda a, b: a + 3,         # constant operand
    lambda a, b: a + a,         # ignores one argument
    lambda a, b: a * b,         # unsupported primitive
    lambda a, b: (a + b) / 2,   # dtype-changing chain
])
def test_builtin_reduce_op_rejects_non_builtins(fn):
    assert builtin_reduce_op(fn, jnp.int32(0), jnp.zeros(4, jnp.int32)) is None


def test_builtin_reduce_op_rejects_unsupported_values():
    add = lambda a, b: a + b  # noqa: E731
    assert builtin_reduce_op(add, np.float64(0),
                             jnp.zeros(4, jnp.float16)) is None
    assert builtin_reduce_op(  # pytree value: not a single leaf
        add, jnp.int32(0),
        {"a": jnp.zeros(4, jnp.int32), "b": jnp.zeros(4, jnp.int32)}) is None
    assert builtin_reduce_op(  # non-scalar identity
        add, jnp.zeros(2, jnp.int32), jnp.zeros(4, jnp.int32)) is None
    assert builtin_reduce_op(  # ndim > 2
        add, jnp.int32(0), jnp.zeros((4, 2, 2), jnp.int32)) is None


# ---------------------------------------------------------------------------
# registry: autotune memo (LRU + single-builder — ISSUE 7 satellite 4)
# ---------------------------------------------------------------------------


def test_tune_memoises_per_key():
    r = KernelRegistry(mode="interpret")
    calls = []
    best = r.tune(("k", 1), (128, 256), lambda b: calls.append(b) or b * 1e-6)
    assert best == 128 and calls == [128, 256]
    assert r.tune(("k", 1), (128, 256), lambda b: 1 / 0) == 128  # memo hit
    assert r.stats["autotune_runs"] == 1


def test_tune_keys_distinguish_ops_and_avals():
    r = KernelRegistry(mode="interpret")
    timer = lambda b: float(b)  # noqa: E731
    for key in (("segment_reduce", "sum", "int32", 256),
                ("segment_reduce", "max", "int32", 256),
                ("segment_reduce", "sum", "int32", 512)):
        r.tune(key, (64, 128), timer)
    assert r.stats["autotune_runs"] == 3


def test_tune_single_candidate_skips_timing():
    r = KernelRegistry(mode="interpret")
    assert r.tune(("k",), (256,), lambda b: 1 / 0) == 256
    assert r.stats["autotune_runs"] == 1  # still counted as a sweep


def test_tune_eviction_retunes_exactly_once():
    r = KernelRegistry(mode="interpret", tune_cache_size=1)
    timer = lambda b: float(b)  # noqa: E731
    for key in (("A",), ("B",), ("A",)):  # B evicts A; A re-tunes
        r.tune(key, (64, 128), timer)
    assert r.stats["autotune_runs"] == 3
    assert r.stats["autotune_evictions"] == 2
    assert r.tune(("A",), (64, 128), timer) == 64  # now memoised again
    assert r.stats["autotune_runs"] == 3


def test_concurrent_misses_on_one_key_cost_one_sweep():
    r = KernelRegistry(mode="interpret")
    calls, gate = [], threading.Event()

    def timer(b):
        calls.append(b)
        gate.wait(5)  # park the builder so every thread reaches tune()
        return float(b)

    threads = [threading.Thread(target=r.tune,
                                args=(("hot",), (64, 128), timer))
               for _ in range(6)]
    for t in threads:
        t.start()
    while not calls:  # one builder is inside the sweep
        pass
    gate.set()
    for t in threads:
        t.join()
    assert r.stats["autotune_runs"] == 1
    assert sorted(calls) == [64, 128]


def test_failed_sweep_unparks_waiters():
    r = KernelRegistry(mode="interpret")
    with pytest.raises(ZeroDivisionError):
        r.tune(("bad",), (64, 128), lambda b: 1 / 0)
    # the key is not poisoned: the next caller re-tunes
    assert r.tune(("bad",), (64, 128), lambda b: float(b)) == 64
    assert r.stats["autotune_runs"] == 1


# ---------------------------------------------------------------------------
# wide-stage equivalence: kernel tier ON vs OFF, every shuffle kind
# ---------------------------------------------------------------------------


def _worker(mode, **props):
    return IWorker(ICluster(IProperties({"ignis.kernels": mode, **props})),
                   "python")


_VALS = np.random.default_rng(2).integers(0, 10_000, 512).astype(np.int32)

# kind → (pipeline, kernel-eligible?) — only reduceByKey runs the segment
# kernel; the p=8 twin is in _distributed_main.py
_KINDS = {
    "sort": (lambda df: df.sort(), False),
    "distinct": (lambda df: df.map(lambda x: x % 17).distinct(), False),
    "reduceByKey": (lambda df: df.map(lambda x: {"key": x % 13, "value": x})
                    .reduce_by_key(lambda a, b: a + b, 0), True),
    "groupByKey": (lambda df: df.map(lambda x: {"key": x % 13, "value": x})
                   .group_by_key(), False),
    "partitionBy": (lambda df: df.map(lambda x: {"key": x % 13, "value": x})
                    .partition_by(), False),
    "join": (lambda df: df.map(lambda x: {"key": x % 5, "value": x})
             .join(df.map(lambda x: {"key": x % 5, "value": x * 2}),
                   max_matches=4), False),
}


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_wide_stage_kernel_on_off_equivalence(kind):
    pipeline, eligible = _KINDS[kind]
    rows, counters = {}, {}
    for mode in ("interpret", "off"):
        w = _worker(mode)
        df = pipeline(w.parallelize(_VALS[:256]))
        rows[mode] = sorted(map(repr, df.collect()))
        s = w.shuffle_stats()
        counters[mode] = (s["overflow_retries"], s["fanout_retries"])
        if mode == "interpret" and eligible:
            assert s["kernel_hits"] >= 1, s
        if mode == "off":
            assert s["kernel_hits"] == 0
    assert rows["interpret"] == rows["off"]
    # the adaptive engine must take the SAME overflow/fan-out trajectory
    # on both tiers (bit-identical routing ⇒ identical retry decisions)
    assert counters["interpret"] == counters["off"]


@pytest.mark.parametrize("op,fn,ident", [
    ("sum", lambda a, b: a + b, 0),
    ("max", jnp.maximum, 0),
    ("min", jnp.minimum, 2**31 - 1),
])
def test_reduce_by_key_kernel_matches_python_oracle(op, fn, ident):
    w = _worker("interpret")
    df = (w.parallelize(_VALS).map(lambda x: {"key": x % 11, "value": x})
          .reduce_by_key(fn, ident))
    got = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
           for r in df.collect()}
    exp = {}
    red = {"sum": lambda a, b: a + b, "max": max, "min": min}[op]
    for v in _VALS:
        k = int(v) % 11
        exp[k] = red(exp[k], int(v)) if k in exp else int(v)
    assert got == exp
    assert w.shuffle_stats()["kernel_hits"] >= 1


def test_aggregate_by_key_rides_the_kernel_tier():
    w = _worker("interpret")
    df = (w.parallelize(_VALS[:256]).map(lambda x: {"key": x % 7, "value": x})
          .aggregate_by_key(0, lambda z, v: z + v % 3, lambda a, b: a + b))
    got = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
           for r in df.collect()}
    exp = {}
    for v in _VALS[:256]:
        exp[int(v) % 7] = exp.get(int(v) % 7, 0) + int(v) % 3
    assert got == exp
    assert w.shuffle_stats()["kernel_hits"] >= 1


def test_non_builtin_fn_falls_back_with_identical_results():
    w_on, w_off = _worker("interpret"), _worker("off")
    rows = {}
    for name, w in (("on", w_on), ("off", w_off)):
        df = (w.parallelize(_VALS[:128])
              .map(lambda x: {"key": x % 5, "value": x})
              .reduce_by_key(lambda a, b: a + b + 1, 0))  # not a builtin
        rows[name] = sorted(map(repr, df.collect()))
    assert rows["on"] == rows["off"]
    # the eligible node consulted the registry and was REJECTED before
    # selection (op recognition) — no hit either way
    assert w_on.shuffle_stats()["kernel_hits"] == 0


def test_float_values_stay_exact_for_integer_data():
    # f32 sums of integer-valued data are associative-exact: the kernel
    # path must match the oracle path to the bit
    fvals = _VALS[:256].astype(np.float32)
    rows = {}
    for mode in ("interpret", "off"):
        df = (_worker(mode).parallelize(fvals)
              .map(lambda x: {"key": x % 9, "value": x})
              .reduce_by_key(lambda a, b: a + b, 0.0))
        rows[mode] = [(int(np.asarray(r["key"])),
                       np.asarray(r["value"]).tobytes())
                      for r in sorted(df.collect(),
                                      key=lambda r: int(np.asarray(r["key"])))]
    assert rows["interpret"] == rows["off"]


# ---------------------------------------------------------------------------
# telemetry: stats surface, explain annotation, repeat-run flatness
# ---------------------------------------------------------------------------


def test_kernel_stats_surface_in_shuffle_stats():
    w = _worker("interpret")
    s = w.shuffle_stats()
    for k in ("kernel_hits", "kernel_fallbacks", "autotune_runs",
              "autotune_evictions"):
        assert k in s, sorted(s)


def test_explain_shows_kernel_annotation_and_tuned_block():
    w = _worker("interpret")
    df = (w.parallelize(_VALS[:256]).map(lambda x: {"key": x % 13, "value": x})
          .reduce_by_key(lambda a, b: a + b, 0))
    df.collect()
    text = df.explain()
    assert "kernel=segment_reduce[interpret]" in text
    assert "op=sum" in text and "block=" in text
    assert "kernels: mode=interpret" in text


def test_repeat_lineage_is_tune_and_compile_flat():
    w = _worker("interpret")

    def run():
        return (w.parallelize(_VALS[:256])
                .map(lambda x: {"key": x % 13, "value": x})
                .reduce_by_key(lambda a, b: a + b, 0).collect())

    first = sorted(map(repr, run()))
    s1 = w.shuffle_stats()
    assert s1["autotune_runs"] >= 1
    for _ in range(2):
        assert sorted(map(repr, run())) == first
    s2 = w.shuffle_stats()
    assert s2["autotune_runs"] == s1["autotune_runs"]
    assert s2["wide_plan_misses"] == s1["wide_plan_misses"]


def test_tuned_block_feeds_the_plan_key():
    # different tuned blocks must not collide in the wide-plan cache:
    # force two registries to tune differently by restricting candidates
    wa = _worker("interpret", **{"ignis.kernels.blocks": "64"})
    wb = _worker("interpret", **{"ignis.kernels.blocks": "128"})
    rows, plans = [], []
    for w in (wa, wb):
        df = (w.parallelize(_VALS[:256])
              .map(lambda x: {"key": x % 13, "value": x})
              .reduce_by_key(lambda a, b: a + b, 0))
        rows.append(sorted(map(repr, df.collect())))
        assert w.shuffle_stats()["kernel_hits"] >= 1
        plans.append(df.explain())
    assert rows[0] == rows[1]
    assert "block=64" in plans[0]
    assert "block=128" in plans[1]


# ---------------------------------------------------------------------------
# compiled backends fail loudly: no cached fallback behind a chip run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["auto", "on"])
def test_compiled_probe_failure_raises(monkeypatch, mode):
    def boom(interpret):
        raise RuntimeError("Mosaic failed to compile TPU kernel: boom")

    monkeypatch.setattr(reg, "compiled_backend", lambda: True)
    monkeypatch.setattr(reg, "_probe_segment_reduce", boom)
    r = KernelRegistry(mode=mode)
    with pytest.raises(reg.KernelUnavailable,
                       match="segment_reduce.*Mosaic failed to compile"):
        r.select()
    assert r.stats["kernel_fallbacks"] == 0
    # nothing was cached: a repaired backend selects the kernel next time
    monkeypatch.setattr(reg, "_probe_segment_reduce", lambda interpret: None)
    sel = r.select()
    assert sel is not None and not sel.interpret


def test_compiled_tune_failure_raises_from_the_wide_stage(monkeypatch):
    # the probe passes, but the sweep's compiled kernel cannot run on this
    # host: the action must raise, naming the kernel, not fall back
    monkeypatch.setattr(reg, "compiled_backend", lambda: True)
    monkeypatch.setattr(reg, "_probe_segment_reduce", lambda interpret: None)
    w = _worker("auto", **{"ignis.kernels.blocks": "1024,2048"})
    vals = np.arange(4096, dtype=np.int32)
    df = (w.parallelize(vals).map(lambda x: {"key": x % 13, "value": x})
          .reduce_by_key(lambda a, b: a + b, 0))
    with pytest.raises(reg.KernelUnavailable, match="segment_reduce.*autotune"):
        df.collect()
    assert w.metrics("kernels")["kernel_fallbacks"] == 0


def test_interpreted_tune_failure_still_degrades():
    r = KernelRegistry(mode="interpret")
    sel = r.select()
    r.degrade(sel, RuntimeError("boom"))
    assert r.stats["kernel_hits"] == 0 and r.stats["kernel_fallbacks"] == 1
