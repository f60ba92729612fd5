"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Set-up builds the cell's job (inputs made on the device from the seed, the
user's workers and resident frames), then runs one warm-up job, which
compiles (or loads from the compilation cache) every program a job uses and
leaves the shuffle capacities that fit in the engine's memory. The window
then runs jobs in a closed loop, one at a time, until ``--seconds`` have
passed; it ends when the last job's answer is back.
After the window the peak device memory is read, the program's state is
freed, and every answer is compared with the plain reference built from the
seed. With ``--trace 1`` the window runs under the JAX profiler and the
result carries the cell's per-layer metrics instead of its end-to-end ones.

Every metric has a reader ``metrics/<name>.py`` with ``read(run) -> float |
None`` (``None``: nothing to read in this cell); the job kind is
``jobs/<traffic["job"]>.py``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell: entries of BENCHMARK.json and the files they name
# ---------------------------------------------------------------------------


def load_spec(path: str = SPEC_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark's directory, by file path
    (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict

    @classmethod
    def find(cls, spec: dict, name: str) -> "Cell":
        wl = next((w for w in spec["workloads"] if w["name"] == name), None)
        if wl is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
        return cls(wl, _load_json(ROOT, entry["file"]),
                   _load_json(HERE, "traffic", wl["traffic"] + ".json"))


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """One run's measurements, as the metric readers get them."""

    cell: str
    window_s: float
    jobs: int
    records: int  # input records of the jobs completed in the window
    setup_s: float
    memory_peak_bytes: int
    compiles: int  # executables built or loaded inside the window
    counters: dict  # program counters, delta over the window
    kernel_shapes: dict
    device_kind: str
    trace: object = None  # xtrace.Trace of the window (--trace 1)
    window_ns: tuple = (0, 0)
    tracer_spans: list = field(default_factory=list)  # JobTracer spans
    devices: int = 1


class CompileCount:
    """Counts JAX's backend compile events (a compile or a persistent-cache
    load: every executable the process builds)."""

    _listening = None

    def __init__(self):
        import jax

        if CompileCount._listening is None:
            CompileCount._listening = self
            jax.monitoring.register_event_duration_secs_listener(self._event)
        self.n = 0

    @staticmethod
    def _event(name, _secs, **_kw):
        if name == COMPILE_EVENT:
            CompileCount._listening.n += 1

    @property
    def count(self) -> int:
        return CompileCount._listening.n


def _peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks)) if peaks else 0


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(argv=None, *, t_start: float | None = None, require_tpu: bool = True,
        spec: dict | None = None, config_overrides: dict | None = None) -> dict:
    """Run the cell named by ``--workload`` once; returns the result line
    (also printed as the last line of stdout). ``require_tpu=False`` and
    ``config_overrides`` (tiny sizes) are for the CPU tests."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    import jax

    spec = load_spec() if spec is None else spec
    cell = Cell.find(spec, args.workload)
    chips = int(cell.workload["chips"])
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell {args.workload} needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    used = devs[:chips]
    cfg = {**cell.config, **(config_overrides or {})}
    if (cell.traffic.get("loop"), cell.traffic.get("in_flight")) != ("closed", 1):
        raise ValueError("the harness runs a closed loop of one job in flight")
    compiles = CompileCount()

    from repro.core import Ignis

    Ignis.start()
    job = load_module("jobs", cell.traffic["job"]).Job(cfg, cell.traffic,
                                                       args.seed, chips)
    job.setup()
    with jax.profiler.TraceAnnotation("bench:warmup"):
        job.run_one()
    setup_s = time.perf_counter() - t_start
    _log(f"setup: {setup_s:.3f} s, {compiles.count} executables built")

    tracer = None
    if args.trace:
        from repro.profile.tracer import JobTracer

        tracer = job.tracer = JobTracer()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)

    answers, attempted, failed = [], 0, 0
    c0, n0 = job.counters(), compiles.count
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    with jax.profiler.TraceAnnotation("bench:window"):
        while True:
            attempted += 1
            with jax.profiler.TraceAnnotation("bench:job"):
                try:
                    answers.append(job.run_one())
                except Exception:  # the run goes on to report it
                    failed += 1
                    _log(traceback.format_exc())
                    break
            if time.perf_counter() >= deadline:
                break
    window_s = time.perf_counter() - t0
    in_window = compiles.count - n0
    counters = {k: v - c0.get(k, 0) for k, v in job.counters().items()}
    if args.trace:
        jax.profiler.stop_trace()
    peak = _peak_bytes(used)
    kernel_shapes, records = dict(job.kernel_shapes), job.records
    tracer_spans = tracer.spans() if tracer is not None else []
    job.tracer = None
    job.release()
    gc.collect()

    # ---- correctness: every answer against the reference -----------------
    t_check = time.perf_counter()
    checks = job.check(answers)
    _log(f"reference and checks: {time.perf_counter() - t_check:.3f} s")
    correct = failed == 0 and bool(answers) and all(c.ok for c in checks)

    run_ = Run(cell=args.workload, window_s=window_s, jobs=len(answers),
               records=len(answers) * records, setup_s=setup_s,
               memory_peak_bytes=peak, compiles=in_window, counters=counters,
               kernel_shapes=kernel_shapes, device_kind=devs[0].device_kind,
               tracer_spans=tracer_spans, devices=chips)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    section = "per_layer" if args.trace else "end_to_end"
    breakdown = None
    if args.trace:
        from benchmarks.chip import xtrace

        path = xtrace.find_xplane(TRACE_DIR)
        run_.trace = xtrace.load(path) if path else xtrace.Trace()
        win = run_.trace.window()
        if win is not None:
            run_.window_ns = (win.start, win.end)
            busy = [xtrace.busy_ns(run_.trace.ops.get(i, []), win.start, win.end)
                    for i in range(chips)]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = win.dur / 1e9
            breakdown = xtrace.breakdown(run_.trace, win.start, win.end)
        shutil.rmtree(os.path.dirname(TRACE_DIR), ignore_errors=True)

    metrics = {}
    for m in spec[section]:
        if not applies(m, args.workload):
            continue
        v = load_module("metrics", m["name"]).read(run_)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    _log(f"window: {window_s:.6f} s, {len(answers)} jobs, {failed} failed, "
         f"{in_window} executables built in the window")
    if job.detail:
        _log(f"check: {job.detail}")
    for c in checks:
        _log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
             f"{'ok' if c.ok else 'FAILED'}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    print(json.dumps(result), flush=True)
    return result
