"""Kernel tier (docs/kernels.md, DESIGN.md §11): per-kernel cost vs the
jnp oracles, wide-stage speedup with the tier on vs off, and the
repeat-run counter gate.

Three row groups:

* ``kern_*`` — the shuffle-tier kernel's stage entry (segment_totals)
  timed under jit against its always-available jnp oracle, in the mode
  the registry would actually pick for this backend
  (compiled on TPU, interpret elsewhere). The ratio is informational
  (no ``target=``): interpreted Pallas is EXPECTED to lose to the oracle
  on CPU — that asymmetry is exactly why auto mode never interprets.
* ``kernels_wide_*`` — terasort-style reduceByKey and a pagerank-style
  join+reduceByKey chain with ``ignis.kernels=auto`` vs ``off``,
  interleaved within each iteration with a per-iteration ratio
  (the bench_hybrid lesson: separate timing blocks let machine-load
  drift skew the headline). The floor is machine-aware and
  self-describing via the row's ``target=`` token
  (tools/check_bench.py): on a compiled-Pallas backend the kernel tier
  must win outright (1.5x); on an interpret-only host auto mode
  selects the bit-identical plain-JAX fallback, so the floor is parity
  with 10% noise headroom (0.9x) — the row then guards "the kernel
  tier's selection layer adds no overhead", not a speedup.
* ``kernels_repeat_warm`` — a repeat lineage on the forced-interpret
  tier must be plan-warm and tune-warm: ``kernel_recompiles`` (wide-plan
  misses during the repeats) and ``kernel_retunes`` (autotune sweeps
  during the repeats) are CI-gated at zero via the counter gate.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timeit
from repro.core import ICluster, IProperties, IWorker
from repro.core.shuffle import segmented_reduce
from repro.kernels.registry import compiled_backend
from repro.kernels.segment_reduce import segment_totals


def _per_kernel_rows(n: int):
    interpret = not compiled_backend()
    tag = "interpret" if interpret else "compiled"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int32))
    keys = jnp.sort(jnp.asarray(rng.integers(0, 512, n).astype(np.int32)))
    valid = jnp.asarray(rng.random(n) < 0.9)

    pairs = [
        ("segment_totals",
         jax.jit(lambda v: segment_totals(keys, valid, v, "sum",
                                          jnp.int32(0), 512, interpret)),
         jax.jit(lambda v: segmented_reduce(keys, valid, v,
                                            jnp.add, jnp.int32(0))), x),
    ]
    rows = []
    for name, kern, oracle, arg in pairs:
        t_k = timeit(lambda: kern(arg), warmup=1, iters=3)
        t_o = timeit(lambda: oracle(arg), warmup=1, iters=3)
        rows.append(row(
            f"kern_{name}", t_k,
            f"mode={tag} oracle_us={t_o*1e6:.1f} n={n}"))
    return rows


def _wide_stage_rows(n: int, iters: int):
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 100_000, n).astype(np.int32)
    edges = rng.integers(0, 64, (max(n // 50, 64), 2)).astype(np.int32)

    def make(mode):
        return IWorker(ICluster(IProperties({"ignis.kernels": mode})),
                       "python")

    def terasort_stage(w):
        return (w.parallelize(vals)
                .map(lambda x: {"key": x % 97, "value": jnp.int32(1)})
                .reduce_by_key(lambda a, b: a + b, 0).count())

    def pagerank_stage(w):
        src = w.parallelize(edges[:, 0]).map(
            lambda s: {"key": s, "value": jnp.float32(1.0)})
        dst = w.parallelize(edges[:, 1]).map(
            lambda d: {"key": d, "value": jnp.float32(0.5)})
        contrib = src.join(dst, max_matches=64).map(
            lambda r: {"key": r["key"], "value": r["value"][0] * r["value"][1]})
        return contrib.reduce_by_key(lambda a, b: a + b, 0.0).count()

    w_auto, w_off = make("auto"), make("off")
    # machine-aware floor (the bench_hybrid precedent): a compiled-Pallas
    # backend must beat the oracle outright; an interpret-only host runs
    # the SAME fallback code in auto mode, so the floor is parity-with-
    # noise-headroom and the row guards selection overhead, not a win
    floor = 1.5 if compiled_backend() else 0.9
    backend = jax.default_backend()
    rows = []
    for name, stage in (("terasort", terasort_stage),
                        ("pagerank", pagerank_stage)):
        stage(w_auto), stage(w_off)  # warm: tunes, plans, capacity memory
        ta, to, ratios = [], [], []
        for _ in range(iters):
            t0 = time.perf_counter()
            stage(w_auto)
            t1 = time.perf_counter()
            stage(w_off)
            t2 = time.perf_counter()
            ta.append(t1 - t0)
            to.append(t2 - t1)
            ratios.append((t2 - t1) / (t1 - t0))
        t_auto = sorted(ta)[len(ta) // 2]
        t_off = sorted(to)[len(to) // 2]
        factor = sorted(ratios)[len(ratios) // 2]
        rows.append(row(f"kernels_wide_{name}_auto", t_auto,
                        f"n={n} backend={backend}"))
        rows.append(row(
            f"kernels_wide_{name}", t_off,
            f"off_vs_auto={factor:.2f}x backend={backend} target={floor}"))
    s = w_auto.shuffle_stats()
    rows.append(row(
        "kernels_auto_selection", 0.0,
        f"hits={s['kernel_hits']} fallbacks={s['kernel_fallbacks']} "
        f"autotune_runs={s['autotune_runs']}"))
    return rows


def _repeat_rows(n: int):
    w = IWorker(ICluster(IProperties({"ignis.kernels": "interpret"})),
                "python")
    vals = np.random.default_rng(2).integers(0, 100_000, n).astype(np.int32)

    def run():
        return (w.parallelize(vals)
                .map(lambda x: {"key": x % 53, "value": x})
                .reduce_by_key(lambda a, b: a + b, 0).count())

    run()  # first lineage: tune + compile
    s1 = w.shuffle_stats()
    t = timeit(run, warmup=0, iters=3)
    s2 = w.shuffle_stats()
    assert s2["kernel_hits"] > s1["kernel_hits"] >= 1, (s1, s2)
    return [row(
        "kernels_repeat_warm", t,
        # both counters are CI-gated at zero (tools/check_bench.py):
        # a repeat lineage must be plan-warm AND tune-warm
        f"kernel_recompiles={s2['wide_plan_misses'] - s1['wide_plan_misses']} "
        f"kernel_retunes={s2['autotune_runs'] - s1['autotune_runs']} "
        f"kernel_hits={s2['kernel_hits']}")]


def bench(n: int = 100_000, iters: int = 3):
    return (_per_kernel_rows(min(n, 1 << 16))
            + _wide_stage_rows(n, iters)
            + _repeat_rows(n))


if __name__ == "__main__":
    from benchmarks.common import emit

    emit(bench())
