#!/usr/bin/env python
"""Chip smoke run: the hybrid dataflow + SPMD path on a TPU, checked
against plain NumPy references.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: aggregation (with a
                                        # hash partition), sort and CG only

Every phase goes through the user-facing API (``Ignis.start``,
``ICluster``/``IWorker``, ``IDataFrame``, ``worker.call``, ``IJob``) and
compares its result with a NumPy reference computed from ``--seed``:

* aggregation: 2^27 ``{key, value}`` int32 records, keys Zipf(1.1) over
  2^16 values, ``parallelize -> map -> reduce_by_key(sum)``, once with
  ``ignis.kernels=auto`` and once with ``off``. The auto run must ride
  ``segment_reduce[compiled]`` with no fallback, and both runs must agree
  to the bit. With four chips a ``partition_by`` precedes the reduce, and
  only the auto run is made.
* sort: 2^27 uniform int32 keys through ``sort()``, checked by count, sum
  and sum of squares (mod 2^32), min, max and adjacent-pair order, all
  reduced on the device.
* stencil: 10 Jacobi sweeps of an 8192 x 8192 f32 grid through
  ``worker.call("stencil_app")``, max abs error <= 1e-6.
* cg: 30 CG iterations on 2^24 unknowns through ``worker.call("cg_app")``,
  relative L2 error <= 1e-4 against a float64 NumPy CG.
* job: the paper's Fig. 12 shape as one ``IJob`` of async branches:
  dataflow prepares a CG right-hand side, ``import_data`` hands it to the
  SPMD worker's ``cg_app``, and the solution comes back as a dataframe
  that dataflow reduces; a second branch counts Zipf keys.

Earlier lines report, for information only, the device, set-up and
per-phase seconds (compiles included) and peak device bytes. The last line
of stdout is one JSON object naming the device; it is printed only when
every phase passed. Without a TPU the script raises before any phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import ICluster, IProperties, IWorker, Ignis, compat  # noqa: E402

AGG_RECORDS = 1 << 27
KEYS = 1 << 16
ZIPF_S = 1.1
SORT_KEYS = 1 << 27
GRID = 8192
STENCIL_ITERS = 10
STENCIL_ATOL = 1e-6
CG_N = 1 << 24
CG_ITERS = 30
CG_RTOL = 1e-4
JOB_N = 1 << 24

I32 = np.iinfo(np.int32)


class SmokeFailure(AssertionError):
    """A phase's result disagrees with its reference."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data (made from the seed, in bulk) and NumPy references
# ---------------------------------------------------------------------------


def zipf_keys(rng, n: int, k: int = KEYS, s: float = ZIPF_S) -> np.ndarray:
    """n keys drawn Zipf(s) over k values by inverse CDF; the ranks are
    shuffled so the hot keys are not the smallest ids."""
    cdf = np.cumsum(np.arange(1, k + 1, dtype=np.float64) ** -s)
    cdf /= cdf[-1]
    u = rng.random(n)
    ids = rng.permutation(k).astype(np.int32)
    out = np.empty(n, np.int32)
    step = 1 << 22

    def fill(lo):
        out[lo:lo + step] = ids[np.searchsorted(cdf, u[lo:lo + step], side="right")]

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(fill, range(0, n, step)))
    return out


def stencil_reference(grid: np.ndarray, iters: int) -> np.ndarray:
    """Jacobi sweeps with periodic boundaries, in the app's f32 op order."""
    u = grid
    for _ in range(iters):
        u = (np.roll(u, 1, 0) + np.roll(u, -1, 0) + np.roll(u, 1, 1)
             + np.roll(u, -1, 1)) * np.float32(0.25)
    return u


def cg_reference(b: np.ndarray, iters: int) -> np.ndarray:
    """CG on tridiag(-1, 2, -1) with Dirichlet boundaries, in float64."""
    b = b.astype(np.float64)
    x, r, q = np.zeros_like(b), b.copy(), b.copy()
    Aq = np.empty_like(b)
    rs = r @ r
    for _ in range(iters):
        np.multiply(q, 2.0, out=Aq)
        Aq[1:] -= q[:-1]
        Aq[:-1] -= q[1:]
        alpha = rs / max(q @ Aq, 1e-30)
        x += alpha * q
        r -= alpha * Aq
        rs_new = r @ r
        q *= rs_new / max(rs, 1e-30)
        q += r
        rs = rs_new
    return x


# ---------------------------------------------------------------------------
# native apps that hand a frame's rows back to the driver, or reduce them
# on the device (run through worker.void_call like any other native app)
# ---------------------------------------------------------------------------


def rows_app(ctx, data=None, valid=None):
    """The frame's rows as device arrays ``(data, valid)``."""
    return data, valid


def sort_stats_app(ctx, data=None, valid=None):
    """Per-executor reductions of a sorted int32 frame: valid count, sum and
    sum of squares (wrapping, i.e. mod 2^32), min, max, and whether the
    rows are in order. Invalid rows sort last with the max key as their
    sentinel, so in-order means every adjacent pair is non-decreasing."""
    mesh, axis = ctx.comm()

    def local(k, v):
        m = jnp.where(v, k, I32.max)
        kv = jnp.where(v, k, 0)
        stats = jnp.stack([
            v.sum(dtype=jnp.int32), kv.sum(), (kv * kv).sum(),
            m.min(), jnp.where(v, k, I32.min).max(),
            jnp.all(m[1:] >= m[:-1]).astype(jnp.int32)])
        return stats[None]

    f = compat.shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(axis))
    return np.asarray(jax.device_get(jax.jit(f)(data, valid)))


@jax.jit
def _dense_totals(key, value, valid):
    """Per-key sum and number of output rows, as dense (KEYS,) arrays."""
    slot = jnp.where(valid, key, KEYS)
    totals = jnp.zeros(KEYS, jnp.int32).at[slot].add(value, mode="drop")
    rows = jnp.zeros(KEYS, jnp.int32).at[slot].add(1, mode="drop")
    return totals, rows


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _worker(chips: int, kind: str = "dataflow", cluster=None, **props):
    if cluster is None:
        cluster = ICluster(IProperties({"ignis.executor.instances": str(chips),
                                        **props}))
    return IWorker(cluster, kind)


def _rows_per_device(valid) -> list:
    """Valid rows of each shard of ``valid``, in shard order, each summed on
    the device that holds it."""
    shards = sorted(valid.addressable_shards, key=lambda s: s.index[0].start or 0)
    return [int(jnp.sum(s.data, dtype=jnp.int32)) for s in shards]


def _kernel_lines(df) -> list[str]:
    return [ln.strip() for ln in df.explain().splitlines()
            if "kernel=" in ln or ln.startswith("kernels:")]


def aggregation(rng, chips: int, n: int = AGG_RECORDS,
                modes=("auto", "off"), tier: str = "compiled"):
    """``modes[0]`` must run on the kernel ``tier``; ``modes[1]`` (one chip
    only) must match it bit for bit."""
    keys = zipf_keys(rng, n)
    vals = rng.integers(0, 100, n, dtype=np.int32)
    ref = np.bincount(keys, weights=vals, minlength=KEYS).astype(np.int64)
    present = np.bincount(keys, minlength=KEYS) > 0
    check(ref.max() <= I32.max, "aggregation reference overflows int32")
    # four chips run the kernel tier only: the oracle twin is the one-chip
    # run's bit-identity check, and each extra sort stage there costs minutes
    # of compile
    modes = modes if chips == 1 else modes[:1]
    outs = {}
    for mode in modes:
        t0 = time.perf_counter()
        w = _worker(chips, **{"ignis.kernels": mode})
        df = w.parallelize({"k": keys, "v": vals}).map(
            lambda r: {"key": r["k"], "value": r["v"]})
        if chips > 1:
            df = df.partition_by(lambda r: r["key"])
        red = df.reduce_by_key(lambda a, b: a + b, 0)
        data, valid = w.void_call(rows_app, red)
        totals, rows = _dense_totals(data["key"], data["value"], valid)
        jax.block_until_ready((totals, rows))
        km = w.metrics("kernels")
        per_dev = _rows_per_device(valid)
        log(f"aggregation[{mode}]: {n} records, {int(present.sum())} keys, "
            f"{time.perf_counter() - t0:.2f}s, output rows per device={per_dev} "
            f"kernel_hits={km['kernel_hits']} "
            f"kernel_fallbacks={km['kernel_fallbacks']} "
            f"overflow_retries={w.metrics('shuffle')['overflow_retries']}")
        for ln in _kernel_lines(red):
            log(f"  {ln}")
        check(len(per_dev) == chips and min(per_dev) > 0,
              f"aggregation[{mode}]: rows not spread over {chips} devices: {per_dev}")
        if mode == "off":
            check(km["kernel_hits"] == 0, "kernels=off ran a kernel")
        else:
            check(f"segment_reduce[{tier}]" in red.explain(),
                  f"aggregation[{mode}] did not run segment_reduce[{tier}]")
            check(km["kernel_fallbacks"] == 0,
                  f"aggregation[{mode}] fell back {km['kernel_fallbacks']} times")
        check(np.array_equal(np.asarray(totals), ref),
              f"aggregation[{mode}] per-key sums differ from NumPy")
        check(np.array_equal(np.asarray(rows), present.astype(np.int32)),
              f"aggregation[{mode}] output rows per key differ from NumPy")
        outs[mode] = (data, valid)
    if len(outs) == 2:
        (da, va), (do, vo) = outs.values()
        same = bool(jnp.array_equal(va, vo)) and all(
            bool(jnp.array_equal(a, b)) for a, b in zip(jax.tree.leaves(da),
                                                        jax.tree.leaves(do)))
        check(same, "aggregation: kernels auto and off differ")
        log(f"aggregation: {' and '.join(outs)} bit-identical")
    log("aggregation: per-key sums match NumPy")


def sort(rng, chips: int, n: int = SORT_KEYS):
    keys = rng.integers(I32.min, I32.max, n, dtype=np.int32, endpoint=True)
    k64 = keys.astype(np.int64)
    ref = (n, int(k64.sum() % 2**32), int((k64 * k64 % 2**32).sum() % 2**32),
           int(keys.min()), int(keys.max()))
    t0 = time.perf_counter()
    w = _worker(chips)
    stats = w.void_call(sort_stats_app, w.parallelize(keys).sort())
    per_dev = stats[:, 0].tolist()
    nonempty = stats[stats[:, 0] > 0]
    got = (int(stats[:, 0].sum()),
           int(stats[:, 1].astype(np.int64).sum() % 2**32),
           int(stats[:, 2].astype(np.int64).sum() % 2**32),
           int(nonempty[:, 3].min()), int(nonempty[:, 4].max()))
    in_order = bool(stats[:, 5].all()) and bool(
        (nonempty[1:, 3] >= nonempty[:-1, 4]).all())
    log(f"sort: {n} keys, {time.perf_counter() - t0:.2f}s, "
        f"valid rows per device={per_dev}, in_order={in_order}")
    check(len(per_dev) == chips and min(per_dev) > 0,
          f"sort: rows not spread over {chips} devices: {per_dev}")
    check(got == ref, f"sort: (count, sum, sumsq, min, max) {got} != NumPy {ref}")
    check(in_order, "sort: output out of order")


def stencil(rng, chips: int, g: int = GRID, iters: int = STENCIL_ITERS):
    grid = rng.random((g, g), dtype=np.float32)
    t0 = time.perf_counter()
    w = _worker(chips, "spmd")
    w.load_library("repro.apps.stencil")
    out = w.call("stencil_app", w.parallelize(grid), iters=iters)
    data, valid = w.void_call(rows_app, out)
    got = np.asarray(data)
    dt = time.perf_counter() - t0
    err = float(np.abs(got - stencil_reference(grid, iters)).max())
    log(f"stencil: {g}x{g} f32, {iters} sweeps, {dt:.2f}s, max_abs_err={err!r} "
        f"(tolerance {STENCIL_ATOL})")
    check(bool(np.asarray(valid).all()) and got.shape == grid.shape,
          "stencil: output rows missing")
    check(err <= STENCIL_ATOL, f"stencil: max abs error {err} > {STENCIL_ATOL}")


def _rel_err(got, ref) -> float:
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def cg(rng, chips: int, n: int = CG_N, iters: int = CG_ITERS):
    b = rng.random(n, dtype=np.float32)
    t0 = time.perf_counter()
    w = _worker(chips, "spmd")
    w.load_library("repro.apps.stencil")
    x_df = w.call("cg_app", w.parallelize(b), iters=iters)
    data, valid = w.void_call(rows_app, x_df)
    x = np.asarray(data)
    per_dev = _rows_per_device(valid)
    dt = time.perf_counter() - t0
    err = _rel_err(x, cg_reference(b, iters))
    log(f"cg: {n} unknowns, {iters} iterations, {dt:.2f}s, "
        f"rows per device={per_dev}, rel_l2_err={err!r} (tolerance {CG_RTOL})")
    check(len(per_dev) == chips and sum(per_dev) == n and min(per_dev) > 0,
          f"cg: rows not spread over {chips} devices: {per_dev}")
    check(err <= CG_RTOL, f"cg: relative error {err} > {CG_RTOL}")


def job(rng, chips: int, n: int = JOB_N, iters: int = CG_ITERS):
    u = rng.random(n, dtype=np.float32)
    keys = zipf_keys(rng, n)
    x_ref = cg_reference(u * np.float32(2.0) + np.float32(1.0), iters)
    t0 = time.perf_counter()
    cluster = ICluster(IProperties({"ignis.executor.instances": str(chips)}))
    dataflow = _worker(chips, "python", cluster)
    spmd = _worker(chips, "spmd", cluster)
    spmd.load_library("repro.apps.stencil")

    rhs = dataflow.parallelize(u).map(lambda v: v * 2.0 + 1.0)
    x = spmd.call("cg_app", spmd.import_data(rhs), iters=iters)
    job = Ignis.job("fig12-cg")
    f_norm = x.map(lambda v: v * v).reduce_async(lambda a, c: a + c, 0.0, job=job)
    f_rows = rhs.count_async(job=job)
    f_hist = dataflow.parallelize(keys).count_by_value_async(job=job)
    norm2, rows, hist = float(f_norm.result()), f_rows.result(), f_hist.result()
    tasks = job.metrics("tasks")
    dt = time.perf_counter() - t0
    err = abs(norm2 - float(x_ref @ x_ref)) / float(x_ref @ x_ref)
    ref_hist = np.bincount(keys, minlength=KEYS)
    got_hist = np.zeros(KEYS, np.int64)
    for k, c in hist.items():
        got_hist[k] = c
    log(f"job: {tasks['tasks']} tasks ({tasks['native']} native, "
        f"{tasks['reshard']} reshard, {tasks['failed']} failed), {dt:.2f}s, "
        f"|x|^2 rel_err={err!r} (tolerance {2 * CG_RTOL}), rows={rows}, "
        f"{len(hist)} keys counted")
    check(tasks["failed"] == 0 and tasks["native"] == 1 and tasks["reshard"] >= 1,
          f"job: unexpected task summary {tasks}")
    check(rows == n, f"job: counted {rows} rows, expected {n}")
    check(err <= 2 * CG_RTOL, f"job: |x|^2 relative error {err} > {2 * CG_RTOL}")
    check(np.array_equal(got_hist, ref_hist), "job: key counts differ from NumPy")


ONE_CHIP_PHASES = (aggregation, sort, stencil, cg, job)
#: the cross-chip paths, cheapest first
FOUR_CHIP_PHASES = (sort, cg, aggregation)


def _peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="four executors: aggregation with a hash partition, "
                         "sort and CG")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    chips = 4 if args.four_chips else 1

    t0 = time.perf_counter()
    Ignis.start()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; jax found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"--four-chips needs 4 TPU devices; jax found {len(devs)}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}, "
        f"executors={chips}, jax {jax.__version__}, seed={args.seed}")
    phases = FOUR_CHIP_PHASES if chips > 1 else ONE_CHIP_PHASES
    rngs = np.random.default_rng(args.seed).spawn(len(phases))
    log(f"setup: {time.perf_counter() - t0:.2f}s")
    for phase, rng in zip(phases, rngs):
        t = time.perf_counter()
        phase(rng, chips)
        gc.collect()  # drop the phase's workers and their device buffers
        log(f"phase {phase.__name__}: ok, {time.perf_counter() - t:.2f}s "
            f"(reference included), peak_bytes_in_use={_peak_bytes()}")
    log(f"total: {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))


if __name__ == "__main__":
    main()
