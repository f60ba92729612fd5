"""End-to-end system behaviour: the unified runtime (paper's contribution)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ICluster, IProperties, IWorker, Ignis
from repro.core.dag import DagEngine


@pytest.fixture
def worker():
    Ignis.start()
    return IWorker(ICluster(IProperties()), "python")


def test_map_filter_count_collect(worker):
    df = worker.parallelize(np.arange(100, dtype=np.int32))
    d2 = df.map(lambda x: x * 2).filter(lambda x: x % 3 == 0)
    assert d2.count() == sum(1 for x in range(100) if (2 * x) % 3 == 0)
    got = sorted(int(x) for x in d2.collect())
    assert got == sorted(2 * x for x in range(100) if (2 * x) % 3 == 0)


def test_reduce_and_aggregate(worker):
    df = worker.parallelize(np.arange(1, 51, dtype=np.int32))
    assert int(df.reduce(lambda a, b: a + b)) == sum(range(1, 51))
    assert int(df.fold(0, lambda a, b: a + b)) == sum(range(1, 51))


def test_reduce_by_key(worker):
    df = worker.parallelize(np.arange(60, dtype=np.int32))
    kv = df.map(lambda x: {"key": x % 7, "value": x})
    got = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
           for r in kv.reduce_by_key(lambda a, b: a + b).collect()}
    exp = {k: sum(x for x in range(60) if x % 7 == k) for k in range(7)}
    assert got == exp


def test_join_inner(worker):
    l = worker.parallelize(np.arange(12, dtype=np.int32)).map(
        lambda x: {"key": x % 4, "value": x})
    r = worker.parallelize(np.arange(8, dtype=np.int32)).map(
        lambda x: {"key": x % 4, "value": x * 10})
    rows = l.join(r).collect()
    got = sorted((int(np.asarray(x["key"])), int(np.asarray(x["value"][0])),
                  int(np.asarray(x["value"][1]))) for x in rows)
    exp = sorted((a % 4, a, b * 10) for a in range(12) for b in range(8)
                 if a % 4 == b % 4)
    assert got == exp


def test_sort_distinct_union(worker):
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 500, 80).astype(np.int32)
    s = [int(x) for x in worker.parallelize(vals).sort().collect()]
    assert s == sorted(int(v) for v in vals)
    d = worker.parallelize(np.array([5, 5, 1, 1, 1, 9], np.int32)).distinct()
    assert sorted(int(x) for x in d.collect()) == [1, 5, 9]
    u = worker.parallelize(np.array([1, 2], np.int32)).union(
        worker.parallelize(np.array([3], np.int32)))
    assert sorted(int(x) for x in u.collect()) == [1, 2, 3]


def test_lazy_evaluation_and_cache(worker):
    df = worker.parallelize(np.arange(10, dtype=np.int32))
    m = df.map(lambda x: x + 1)
    assert m.node.compute_count == 0  # nothing ran yet (lazy, paper §4.1)
    m.cache()
    m.count()
    m.count()
    assert m.node.compute_count == 1  # cached: computed once


def test_lineage_recovery(worker):
    df = worker.parallelize(np.arange(40, dtype=np.int32), blocks=4)
    m1 = df.map(lambda x: x + 1).persist()
    m2 = m1.map(lambda x: x * 2).persist()
    assert m2.count() == 40
    c1 = m1.node.compute_count
    DagEngine.kill_block(m2.node, 2)  # lose one executor's cached block
    assert m2.count() == 40
    assert m1.node.compute_count == c1  # cached ancestor untouched
    assert worker.engine.stats["block_recomputes"] == 1  # only the lost block


def test_import_data_between_workers(worker):
    cluster = worker.cluster
    w2 = IWorker(cluster, "cpp")
    df = worker.parallelize(np.arange(16, dtype=np.int32)).map(lambda x: x * 3)
    imported = w2.import_data(df)
    assert sorted(int(x) for x in imported.collect()) == [3 * x for x in range(16)]


def test_spark_mode_parity(worker):
    """spark mode must be numerically identical — only slower (the pipe)."""
    ws = IWorker(ICluster(IProperties({"ignis.mode": "spark"})), "python")
    data = np.arange(50, dtype=np.int32)
    for w in (worker, ws):
        kv = w.parallelize(data).map(lambda x: {"key": x % 5, "value": x})
        out = {int(np.asarray(r["key"])): int(np.asarray(r["value"]))
               for r in kv.reduce_by_key(lambda a, b: a + b).collect()}
        assert out == {k: sum(x for x in range(50) if x % 5 == k) for k in range(5)}


def test_group_by_key(worker):
    df = worker.parallelize(np.arange(20, dtype=np.int32))
    g = df.map(lambda x: {"key": x % 3, "value": x}).group_by_key(group_capacity=8)
    rows = g.collect()
    assert len(rows) == 3
    for r in rows:
        k = int(np.asarray(r["key"]))
        members = sorted(int(v) for v, m in
                         zip(np.asarray(r["value"]["items"]),
                             np.asarray(r["value"]["mask"])) if m)
        assert members == [x for x in range(20) if x % 3 == k]


def test_count_by_value_and_sample(worker):
    cbv = worker.parallelize(np.array([1, 1, 2, 5, 5, 5], np.int32)).count_by_value()
    assert cbv == {1: 2, 2: 1, 5: 3}
    s = worker.parallelize(np.arange(1000, dtype=np.int32)).sample(0.3, seed=1)
    assert 200 < s.count() < 400


def test_properties_system():
    p = IProperties({"ignis.executor.memory": "2GB"})
    assert p.get_bytes("ignis.executor.memory") == 2 * 2**30
    assert p.get_int("ignis.executor.instances") == 1
    assert "ignis.mode" in p
    v = p.view("ignis.executor.")
    assert "ignis.executor.memory" in v


def test_speculative_evaluation(worker):
    """Straggler mitigation: deadline-based duplicate execution."""
    df = worker.parallelize(np.arange(20, dtype=np.int32)).map(lambda x: x + 1)
    blocks = worker.engine.evaluate_speculative(df.node, timeout_s=30.0)
    assert len(blocks) == 1
    # force the speculative path with an immediate deadline
    df2 = worker.parallelize(np.arange(20, dtype=np.int32)).map(lambda x: x * 2)
    blocks2 = worker.engine.evaluate_speculative(df2.node, timeout_s=0.0)
    assert len(blocks2) == 1
    assert worker.engine.stats.get("speculative_retries", 0) >= 1


def test_sample_by_key_and_take_sample(worker):
    kv = worker.parallelize(np.arange(400, dtype=np.int32)).map(
        lambda x: {"key": x % 2, "value": x})
    s = kv.sample_by_key({0: 1.0, 1: 0.0}, seed=3)
    rows = s.collect()
    assert all(int(np.asarray(r["key"])) == 0 for r in rows)
    assert len(rows) == 200
    ts = kv.take_sample(10, seed=1)
    assert len(ts) == 10


def test_foreach(worker):
    seen = []
    worker.parallelize(np.arange(5, dtype=np.int32)).foreach(
        lambda r: seen.append(int(np.asarray(r))))
    assert sorted(seen) == [0, 1, 2, 3, 4]


@pytest.fixture
def cache_config():
    """Restore jax's compile-cache path after a test moves it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_start_leaves_an_env_placed_compile_cache_alone(cache_config, monkeypatch,
                                                        tmp_path):
    # jax reads JAX_COMPILATION_CACHE_DIR itself; the repo must not override it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache_config.update("jax_compilation_cache_dir", str(tmp_path))
    Ignis.start()
    assert cache_config.jax_compilation_cache_dir == str(tmp_path)


def test_start_places_the_compile_cache_in_the_checkout(cache_config, monkeypatch):
    from pathlib import Path

    from repro.core.cluster import COMPILE_CACHE_DIR

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    Ignis.start()
    checkout = Path(__file__).resolve().parents[1]
    assert COMPILE_CACHE_DIR == checkout / ".jax_cache"
    assert cache_config.jax_compilation_cache_dir == str(checkout / ".jax_cache")


def test_chip_smoke_refuses_a_host_without_a_tpu():
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, str(root / "chip_smoke.py")], cwd=root,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "found platform 'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
