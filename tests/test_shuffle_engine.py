"""Adaptive shuffle engine (DESIGN.md §6): capacity memory, fused wide
stages, deferred overflow checks, join fan-out retry/memory, telemetry —
plus the max/min argselect regression (ISSUE 2 satellite).

Exchange-capacity overflow needs p > 1 and is covered in the 8-device
subprocess suite (tests/_distributed_main.py); here we cover everything
observable at p = 1, including join fan-out overflow (which is p-independent).
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ICluster, IProperties, IWorker
from repro.core import shuffle as sh
from repro.core.partition import Block


@pytest.fixture
def worker():
    return IWorker(ICluster(IProperties()), "python")


# ---------------------------------------------------------------------------
# capacity memory + wide-plan cache
# ---------------------------------------------------------------------------


def test_second_action_hits_memory_and_never_recompiles(worker):
    vals = np.random.default_rng(0).integers(0, 500, 96).astype(np.int32)
    srt = worker.parallelize(vals).sort()
    assert [int(x) for x in srt.collect()] == sorted(int(v) for v in vals)
    s1 = worker.shuffle_stats()
    assert s1["capacity_memory_misses"] >= 1
    assert s1["wide_plan_misses"] >= 1
    assert [int(x) for x in srt.collect()] == sorted(int(v) for v in vals)
    s2 = worker.shuffle_stats()
    assert s2["capacity_memory_hits"] > s1["capacity_memory_hits"]
    assert s2["wide_plan_misses"] == s1["wide_plan_misses"]  # zero recompiles
    assert s2["wide_plan_hits"] > s1["wide_plan_hits"]
    assert s2["overflow_retries"] == 0


def test_capacity_memory_survives_lineage_rebuild(worker):
    """Structural signatures: re-building an identical pipeline (fresh lambda
    objects, same code) maps to the same capacity-memory slot and compiled
    wide plan — the benchmark-loop / iterative-driver case."""

    def run():
        return (
            worker.parallelize(np.arange(64, dtype=np.int32))
            .map(lambda x: x % 7)
            .sort()
            .count()
        )

    assert run() == 64
    s1 = worker.shuffle_stats()
    assert run() == 64
    s2 = worker.shuffle_stats()
    assert s2["capacity_memory_hits"] > s1["capacity_memory_hits"]
    assert s2["wide_plan_misses"] == s1["wide_plan_misses"]


def test_fused_wide_stage_reduce_by_key_reuses_plan(worker):
    kv = worker.parallelize(np.arange(60, dtype=np.int32)).map(
        lambda x: {"key": x % 7, "value": x})
    red = kv.reduce_by_key(lambda a, b: a + b)
    exp = {k: sum(x for x in range(60) if x % 7 == k) for k in range(7)}
    got = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
           for r in red.collect()}
    assert got == exp
    m1 = worker.shuffle_stats()["wide_plan_misses"]
    got2 = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
            for r in red.collect()}
    assert got2 == exp
    s = worker.shuffle_stats()
    assert s["wide_plan_misses"] == m1
    assert s["wide_plan_hits"] >= 1


def test_partition_by_preserves_rows(worker):
    kv = worker.parallelize(np.arange(32, dtype=np.int32)).map(
        lambda x: {"key": x % 4, "value": x})
    for pb in (kv.partition_by(), kv.partition_by(lambda r: r["key"])):
        vals = sorted(int(np.asarray(r["value"])) for r in pb.collect())
        assert vals == list(range(32))


# ---------------------------------------------------------------------------
# join fan-out overflow: retry + fan-out memory (p-independent)
# ---------------------------------------------------------------------------


def test_join_fanout_overflow_retries_then_remembers(worker):
    # one hot key with 8 matches per row against max_matches=1: the fan-out
    # bound must double 1→2→4→8 (3 retries), results exactly the oracle
    L = worker.parallelize(np.arange(8, dtype=np.int32)).map(
        lambda x: {"key": x * 0, "value": x})
    R = worker.parallelize(np.arange(8, dtype=np.int32)).map(
        lambda x: {"key": x * 0, "value": x + 100})
    j = L.join(R, max_matches=1)
    got = sorted((int(np.asarray(r["value"][0])), int(np.asarray(r["value"][1])))
                 for r in j.collect())
    assert got == sorted((a, b + 100) for a in range(8) for b in range(8))
    s1 = worker.shuffle_stats()
    assert s1["fanout_retries"] >= 3
    # second run: fan-out memory starts at the fitted bound — no new retries
    assert len(j.collect()) == 64
    s2 = worker.shuffle_stats()
    assert s2["fanout_retries"] == s1["fanout_retries"]
    assert s2["wide_plan_misses"] == s1["wide_plan_misses"]


# ---------------------------------------------------------------------------
# the sort stage's carrying sort vs the argsort-plus-gather oracle
# ---------------------------------------------------------------------------


def _oracle_valid_rows(data, valid, key_fn, ascending):
    """The valid rows in the order ``argsort(where(valid, keys, sentinel))``
    followed by one gather per leaf gives them."""
    keys = jax.vmap(key_fn)(data)
    if not ascending:
        keys = -keys
    order = jnp.argsort(jnp.where(valid, keys, sh._sentinel(keys.dtype)), stable=True)
    keep = np.asarray(valid[order])
    return jax.tree.map(lambda x: np.asarray(x[order])[keep], data)


def _sort_case(case, n=96):
    rng = np.random.default_rng(7)
    valid = rng.random(n) < 0.7
    ints = rng.integers(-50, 50, n).astype(np.int32)
    if case == "max_key_after_invalid":
        top = np.iinfo(np.int32).max
        valid[:8] = False
        ints[8:12] = top  # valid sentinel-valued keys behind invalid rows
        ints[rng.random(n) < 0.1] = top
        return ints, valid, lambda r: r, True
    if case.startswith("float32"):
        f = (ints / 4).astype(np.float32)
        f[:4] = [0.0, -0.0, np.inf, -np.inf]
        return f, valid, lambda r: r, case.endswith("asc")
    if case.startswith("int32"):
        return ints, valid, lambda r: r, case.endswith("asc")
    if case == "kv":
        return {"key": ints % 7, "value": ints}, valid, lambda r: r["key"], True
    assert case == "tree_2d_leaf"
    vec = rng.standard_normal((n, 3)).astype(np.float32)
    return {"key": ints % 7, "vec": vec}, valid, lambda r: r["key"], True


@pytest.mark.parametrize("case,gathers", [
    ("int32_asc", 0), ("int32_desc", 0), ("float32_asc", 0), ("float32_desc", 0),
    ("kv", 0), ("tree_2d_leaf", 1), ("max_key_after_invalid", 0)])
def test_sort_stage_matches_argsort_gather_oracle(worker, case, gathers):
    data, valid, key_fn, ascending = _sort_case(case)
    data = jax.tree.map(jnp.asarray, data)
    valid = jnp.asarray(valid)
    before = worker.shuffle.stats["sort_gathers"]
    out = worker.shuffle.sort(("carry", case), Block(data, valid), key_fn, ascending)
    got_valid = np.asarray(out.valid)
    n_valid = int(np.asarray(valid).sum())
    # every valid row sorts before every invalid row
    assert got_valid[:n_valid].all() and not got_valid[n_valid:].any()
    want = _oracle_valid_rows(data, valid, key_fn, ascending)
    got = jax.tree.map(lambda x: np.asarray(x)[got_valid], out.data)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.tobytes() == w.tobytes()  # bit-identical, -0.0 and order included
    assert worker.shuffle.stats["sort_gathers"] - before == gathers
    assert f"sort_gathers={worker.shuffle.stats['sort_gathers']}" in worker.shuffle.summary()


def _records(n=160, seed=11):
    """Rows with a 3-leaf key (uint32, int32, uint16) that ties in its
    leading leaves and holds each dtype's largest value (the sort's
    sentinel), a 2-D and a 1-D payload leaf, and invalid rows."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 3, n).astype(np.uint32)
    b = rng.integers(-2, 2, n).astype(np.int32)
    c = rng.integers(0, 4, n).astype(np.uint16)
    a[rng.random(n) < 0.15] = np.iinfo(np.uint32).max
    b[rng.random(n) < 0.15] = np.iinfo(np.int32).max
    b[rng.random(n) < 0.1] = np.iinfo(np.int32).min
    c[rng.random(n) < 0.15] = np.iinfo(np.uint16).max
    data = {"a": a, "b": b, "c": c, "row": np.arange(n, dtype=np.int32),
            "pay": rng.integers(0, 2**31, (n, 5)).astype(np.uint32)}
    valid = rng.random(n) < 0.8
    valid[:6] = False
    return data, valid


TUPLE_KEYS = {2: lambda r: (r["a"], r["b"]), 3: lambda r: (r["a"], r["b"], r["c"])}


def check_tuple_sort(data, valid, got_data, got_valid, leaves, ascending):
    """NumPy reference of a tuple-key sort: every valid row once, its
    payload intact, and the valid rows, read in order, in lexicographic
    order of their keys (each leaf in its dtype's order)."""
    got_valid = np.asarray(got_valid)
    got = {k: np.asarray(x)[got_valid] for k, x in got_data.items()}
    names = ["a", "b", "c"][:leaves]
    rows = np.nonzero(valid)[0]
    order = rows[np.lexsort([data[k][rows] for k in reversed(names)])]
    if not ascending:
        order = order[::-1]
    for k in names:  # the key sequence, in order
        assert np.array_equal(got[k], data[k][order]), k
    assert sorted(got["row"].tolist()) == rows.tolist()  # each row once
    for k in data:  # every leaf of each row travelled with its row
        assert np.array_equal(got[k], data[k][got["row"]]), k


@pytest.mark.parametrize("ascending", [True, False])
@pytest.mark.parametrize("leaves", [2, 3])
def test_sort_by_tuple_key_matches_numpy_reference(worker, leaves, ascending):
    data, valid = _records()
    b = Block(jax.tree.map(jnp.asarray, data), jnp.asarray(valid))
    out = worker.shuffle.sort(("tuple", leaves), b, TUPLE_KEYS[leaves], ascending)
    n_valid = int(valid.sum())  # on one executor, valid rows come first
    assert np.asarray(out.valid)[:n_valid].all() and not np.asarray(out.valid)[n_valid:].any()
    check_tuple_sort(data, valid, out.data, out.valid, leaves, ascending)


def test_sort_by_tuple_key_through_the_dataframe(worker):
    data, valid = _records()
    keep = {k: v[valid] for k, v in data.items()}
    keep["row"] = np.arange(int(valid.sum()), dtype=np.int32)
    df = worker.parallelize(keep).sort_by(TUPLE_KEYS[3])
    got = df.collect()
    keys = [(int(r["a"]), int(r["b"]), int(r["c"])) for r in got]
    assert keys == sorted(zip(*(keep[k].tolist() for k in "abc")))
    for r in got:
        assert np.array_equal(np.asarray(r["pay"]), keep["pay"][int(r["row"])])


@pytest.mark.parametrize("case", ["int32_min", "uint32", "uint8", "bool"])
def test_descending_order_is_exact_for_every_dtype(worker, case):
    # a negated key wraps at INT32_MIN and misorders every unsigned key
    rng = np.random.default_rng(3)
    if case == "int32_min":
        x = rng.integers(-5, 5, 64).astype(np.int32)
        x[::7] = np.iinfo(np.int32).min
        x[3::9] = np.iinfo(np.int32).max
    elif case == "bool":
        x = rng.random(64) < 0.5
    else:
        dt = np.dtype(case)
        x = rng.integers(0, np.iinfo(dt).max, 64, endpoint=True).astype(dt)
        x[::5] = 0
        x[1::11] = np.iinfo(dt).max
    got = [np.asarray(v).item() for v in worker.parallelize(x).sort(ascending=False).collect()]
    assert got == sorted(x.tolist(), reverse=True)


@pytest.mark.parametrize("ascending", [True, False])
def test_float_keys_sort_nan_last_in_both_orders(worker, ascending):
    f = np.asarray([1.5, np.nan, -0.0, 0.0, -2.0, np.nan, np.inf, -np.inf], np.float32)
    got = np.asarray([np.asarray(v).item()
                      for v in worker.parallelize(f).sort(ascending=ascending).collect()])
    want = np.sort(f) if ascending else -np.sort(-f)
    assert np.array_equal(got, want, equal_nan=True)


@pytest.mark.parametrize("case", ["int32_min", "uint32"])
def test_top_takes_the_largest_keys(worker, case):
    x = np.arange(40, dtype=np.int32) - 20
    if case == "int32_min":
        x[5] = np.iinfo(np.int32).min
    else:
        x = (x.astype(np.int64) % 2**32).astype(np.uint32)  # negatives wrap high
    got = [np.asarray(v).item() for v in worker.parallelize(x).top(4)]
    assert got == sorted(x.tolist(), reverse=True)[:4]


def test_sort_counters_count_key_leaves_and_bytes(worker):
    data, valid = _records(n=64)
    b = Block(jax.tree.map(jnp.asarray, data), jnp.asarray(valid))
    before = dict(worker.shuffle.stats)
    worker.shuffle.sort(("count", 3), b, TUPLE_KEYS[3])
    worker.shuffle.sort(("count", 3), b, TUPLE_KEYS[3])  # a plan hit: leaves once
    d = {k: worker.shuffle.stats[k] - before[k] for k in before}
    # a, b, c ride as keys, row rides, pay (5 uint32 a row) is gathered
    row = 4 + 4 + 2 + 4 + 20
    assert d["sort_key_leaves"] == 3 and d["sort_gathers"] == 1
    assert d["sort_bytes"] == 2 * 64 * row
    assert d["sort_gather_bytes"] == 2 * 64 * 20
    # one executor sends nothing; on four, p slices of each of the six
    # leaves the local sort returns (valid, a, b, c, row, pay)
    assert d["bucket_slices"] == 0
    assert sh.sort_traffic(TUPLE_KEYS[3](b.data), b.data, 4)["bucket_slices"] == 4 * 6
    # descending: the keys are new arrays, each an operand beside its leaf
    worker.shuffle.sort(("count", 1), b, lambda r: r["a"], False)
    assert worker.shuffle.stats["sort_key_leaves"] - before["sort_key_leaves"] == 4
    assert (worker.shuffle.stats["sort_bytes"] - before["sort_bytes"]
            == 2 * 64 * row + 64 * (row + 4))
    assert "sorts: key_leaves=4" in worker.shuffle.summary()


def _sort_operands(text: str) -> list:
    """The operand types of each ``stablehlo.sort`` in a lowered module."""
    return [re.findall(r"tensor<\d+x(\w+)>", m)
            for m in re.findall(r"stablehlo\.sort\"?\(.*?\n?.*?\}\) : \(([^)]*)\)",
                                text, flags=re.S)]


@pytest.mark.parametrize("case,operands,stable", [
    ("is", ["i1", "i32"], "false"), ("wordcount", ["i1", "i32", "i32"], "true")])
def test_scalar_key_stage_lowers_to_the_same_sort(worker, case, operands, stable):
    # one scalar key: (~valid, key) and the 1-D payload, as before tuple keys
    n = 128
    keys = jnp.arange(n, dtype=jnp.int32)[::-1]
    data = keys if case == "is" else {"key": keys, "value": keys % 3}
    key_fn = (lambda r: r) if case == "is" else (lambda r: r["key"])
    ctx = worker.context

    def stage(d, v):
        return sh.sort_stage(ctx, jax.vmap(key_fn)(d), v, d, n)

    text = jax.jit(stage).lower(data, jnp.ones(n, bool)).as_text()
    assert _sort_operands(text) == [operands]
    assert f"is_stable = {stable}" in text


@pytest.mark.parametrize("op", ["distinct", "reduceByKey", "groupByKey", "join"])
def test_tuple_key_refused_by_other_ops(worker, op):
    kv = worker.parallelize(np.arange(12, dtype=np.int32)).map(
        lambda x: {"key": (x % 3, x % 2), "value": x})
    frame = {"distinct": lambda: kv.distinct(lambda r: r["key"]),
             "reduceByKey": lambda: kv.reduce_by_key(lambda a, b: a + b),
             "groupByKey": lambda: kv.group_by_key(),
             "join": lambda: kv.join(kv)}[op]()
    with pytest.raises(TypeError, match=op):
        frame.collect()


# ---------------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------------


def test_shuffle_stats_keys_and_explain_annotations(worker):
    srt = worker.parallelize(np.arange(16, dtype=np.int32)).map(
        lambda x: x * 3).sort_by(lambda x: x)
    srt.count()
    stats = worker.shuffle_stats()
    for k in ("exchanges", "overflow_retries", "fanout_retries",
              "overflow_checks", "capacity_memory_hits",
              "capacity_memory_misses", "wide_plan_hits", "wide_plan_misses",
              "bytes_moved"):
        assert k in stats, k
    out = srt.explain()
    assert "== shuffle ==" in out
    assert "capacity_factor=" in out and "(memory)" in out
    assert "capacity_memory:" in out and "wide plans:" in out
    assert worker.explain(srt) == out


def test_cold_wide_node_annotated_cold(worker):
    srt = worker.parallelize(np.arange(8, dtype=np.int32)).sort()
    assert "(cold)" in srt.explain()  # never evaluated → no memory entry


# ---------------------------------------------------------------------------
# max/min with key_fn (ISSUE 2 satellite regression)
# ---------------------------------------------------------------------------


def test_max_min_without_key_fn_elementwise(worker):
    df = worker.parallelize(np.array([3, 9, 1, 7], np.int32))
    assert int(df.max()) == 9
    assert int(df.min()) == 1
    dff = worker.parallelize(np.array([3.5, -2.25, 7.75], np.float32))
    assert float(dff.max()) == 7.75
    assert float(dff.min()) == -2.25


def test_max_min_key_fn_returns_arg_row(worker):
    df = worker.parallelize(np.array([3, 9, 1, 7], np.int32))
    # key_fn no longer ignored: negated key flips the winner
    assert int(df.max(lambda x: -x)) == 1
    assert int(df.min(lambda x: -x)) == 9
    kv = worker.parallelize(np.arange(60, dtype=np.int32)).map(
        lambda x: {"key": x % 7, "value": x})
    top = kv.max(lambda r: r["value"])
    assert (int(top["key"]), int(top["value"])) == (59 % 7, 59)
    bot = kv.min(lambda r: r["value"])
    assert (int(bot["key"]), int(bot["value"])) == (0, 0)


def test_max_min_key_fn_respects_validity_mask(worker):
    df = worker.parallelize(np.arange(10, dtype=np.int32)).filter(
        lambda x: x < 5)
    assert int(df.max(lambda x: x)) == 4  # masked rows 5..9 never win
    assert int(df.min(lambda x: -x)) == 4


def test_max_min_key_fn_empty_raises(worker):
    empty = worker.parallelize(np.arange(4, dtype=np.int32)).filter(
        lambda x: x < 0)
    with pytest.raises(ValueError):
        empty.max(lambda x: x)
    with pytest.raises(ValueError):
        empty.min(lambda x: x)


def test_fn_tokens_do_not_collide_across_instances_or_dtypes(worker):
    """Bound methods carry behavior in __self__, and 1 == 1.0 == True in
    Python but not in XLA: neither may share a compiled wide plan."""
    from repro.core.shuffle_plan import fn_token

    class Scaler:
        def __init__(self, k):
            self.k = k

        def key(self, r):
            return r * self.k

    assert fn_token(Scaler(1).key) != fn_token(Scaler(-1).key)

    def mk(a):
        return lambda x: x * a

    assert fn_token(mk(1)) != fn_token(mk(1.0))
    assert fn_token(mk(1)) != fn_token(mk(True))
    assert fn_token(mk(2)) == fn_token(mk(2))  # rebuilds still match

    vals = np.array([3, 9, 1, 7], np.int32)
    up = [int(x) for x in worker.parallelize(vals).sort_by(Scaler(1).key).collect()]
    dn = [int(x) for x in worker.parallelize(vals).sort_by(Scaler(-1).key).collect()]
    assert up == [1, 3, 7, 9]
    assert dn == [9, 7, 3, 1]


def test_fn_token_tracks_referenced_globals(worker):
    """A rebuilt lambda whose referenced module global changed must NOT
    reuse the plan compiled against the old value."""
    import sys
    import types

    from repro.core.shuffle_plan import fn_token

    mod = types.ModuleType("shuffle_token_probe")
    sys.modules["shuffle_token_probe"] = mod
    exec("SCALE = 3\ndef make():\n    return lambda x: x * SCALE\n", mod.__dict__)
    t1 = fn_token(mod.make())
    mod.SCALE = 5
    assert fn_token(mod.make()) != t1
    mod.SCALE = 3
    assert fn_token(mod.make()) == t1  # restored value matches again

    # end to end: second build after the global changed computes fresh
    d = np.arange(6, dtype=np.int32)
    mod.SCALE = 3
    out1 = sorted(int(x) for x in
                  worker.parallelize(d).map(mod.make()).map(lambda x: x + 0).collect())
    assert out1 == [0, 3, 6, 9, 12, 15]
    mod.SCALE = 5
    out2 = sorted(int(x) for x in
                  worker.parallelize(d).map(mod.make()).map(lambda x: x + 0).collect())
    assert out2 == [0, 5, 10, 15, 20, 25]
    del sys.modules["shuffle_token_probe"]


def test_static_token_fingerprints_large_arrays():
    """repr() truncates big arrays; identity tokens must hash the bytes."""
    from repro.core.shuffle_plan import _static_token

    a = np.zeros(2000)
    b = np.zeros(2000)
    b[1000] = 7.0
    assert _static_token(a) != _static_token(b)
    assert _static_token(np.zeros(2000)) == _static_token(np.zeros(2000))


def test_join_unresolvable_fanout_raises_not_truncates(worker):
    """A key too skewed for MAX_ATTEMPTS doublings must raise — overflow is
    detected, never silently dropped (DESIGN.md §1)."""
    L = worker.parallelize(np.arange(1, dtype=np.int32)).map(
        lambda x: {"key": x * 0, "value": x})
    R = worker.parallelize(np.arange(600, dtype=np.int32)).map(
        lambda x: {"key": x * 0, "value": x})
    with pytest.raises(RuntimeError, match="max_matches"):
        L.join(R, max_matches=2).collect()
    assert len(L.join(R, max_matches=600).collect()) == 600


def test_spark_mode_shuffle_parity(worker):
    """The manager runs identically under the spark pipe — only slower."""
    ws = IWorker(ICluster(IProperties({"ignis.mode": "spark"})), "python")
    data = np.random.default_rng(3).integers(0, 99, 40).astype(np.int32)
    outs = []
    for w in (worker, ws):
        outs.append([int(x) for x in w.parallelize(data).sort().collect()])
    assert outs[0] == outs[1] == sorted(int(v) for v in data)


@pytest.mark.parametrize("dtype", ["bool", "int8", "uint8", "int16", "float16",
                                   "bfloat16", "int32", "float32"])
def test_exchange_widening_round_trips_bit_for_bit(dtype):
    # exchanged leaves travel 32 bits wide (shuffle._exchange); narrowing
    # must give back every bit, NaN payloads and -0.0 included
    bits = np.random.default_rng(0).integers(0, 1 << 16, 512).astype(np.uint16)
    if dtype == "bool":
        x = jnp.asarray(bits % 2 == 1)
    else:
        dt = jnp.dtype(dtype)
        raw = jnp.asarray(bits).astype(jnp.dtype(f"uint{8 * dt.itemsize}"))
        x = raw.astype(dt) if dt.itemsize == 4 else jax.lax.bitcast_convert_type(raw, dt)
    wide = sh._widen(x)
    assert wide.dtype.itemsize == 4
    back = sh._narrow(wide, x.dtype)
    assert back.dtype == x.dtype
    assert np.asarray(back).tobytes() == np.asarray(x).tobytes()
