"""The program's own spans (``repro.profile.spans``) beside the device trace.

A traced run hands the readers two records of the window. The device ops and
the benchmark's ``bench:`` spans come from the ``.xplane.pb``, in
nanoseconds from the start of the profile. The program's spans come as the
``TraceBuffer`` copies of its ``ignis:`` spans (``run.tracer_spans``), in
seconds on the profiler's host clock. The two differ by one constant, the
profile's start time, which the reduced trace does not keep. ``offset_ns``
finds it from the window's last job: its last program span ends just before
its answer returns, when ``bench:wait`` (or ``bench:job``) ends, to within
the waiting thread's wake-up. A program without these spans (no
``collect:``, ``compile:``, ``stage:``, ``wide:``, ``import:`` or
``native:`` span in its buffer) gives no reading.
"""
from __future__ import annotations

import re
import sys

from benchmarks.chip import xtrace

#: spans of host work the device may wait for (fetch: is a wait for it)
HOST_WORK = ("collect:", "compile:", "stage:", "wide:", "import:", "native:")
TASK_PHASES = ("compute", "settle", "lock_wait")


def ignis_name(s) -> str:
    """A buffer span's name in the profiler's trace, without ``ignis:``:
    a task's own span is ``task:<kind>`` there."""
    if s.cat == "task" and s.name not in TASK_PHASES:
        return "task:" + str(s.args.get("kind", ""))
    return s.name


def label(s) -> str:
    """``ignis_name``, and for a task's own spans the task's action or
    operator too: ``compute@reduce``, ``task:action@countByValue``."""
    task = s.args.get("task") if s.cat in ("task", "sched") else None
    if not task:
        return ignis_name(s)
    return f"{ignis_name(s)}@{re.sub(r'#[0-9]+', '', task.split('(')[0])}"


def host_work(run) -> list:
    """The program's host-work spans of the window."""
    return [s for s in run.tracer_spans if s.name.startswith(HOST_WORK)]


def offset_ns(run) -> int | None:
    """Trace time minus buffer time, in ns (see the module docstring)."""
    lo, hi = run.window_ns
    if run.trace is None or hi <= lo or not run.tracer_spans:
        return None
    ends = [s for s in run.trace.host if lo <= s.start < hi
            and s.name in ("bench:wait", "bench:job")]
    waits = [s for s in ends if s.name == "bench:wait"] or ends
    if not waits:
        return None
    last = max(s.t1 for s in run.tracer_spans)
    return waits[-1].end - round(last * 1e9)


def on_trace_clock(spans, off: int, name=ignis_name) -> list:
    """Buffer spans as ``xtrace.Span``s on the trace's clock."""
    return [xtrace.Span(name(s), round(s.t0 * 1e9) + off,
                        round(s.t1 * 1e9) + off) for s in spans]


def overlap_ns(a, b) -> int:
    """Length of the intersection of two lists of disjoint sorted
    ``(start, end)`` intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def host_bound_idle_ms_per_job(run) -> float | None:
    """Device idle time of the window inside a host-work span on any
    thread, mean over the cell's devices, per job, in ms."""
    work, off = host_work(run), offset_ns(run)
    lo, hi = run.window_ns
    if not work or off is None or not run.jobs or not run.trace.ops:
        return None
    busy_host = xtrace.merged(xtrace.clip(on_trace_clock(work, off), lo, hi))
    idle = [overlap_ns(xtrace.gaps(run.trace.ops.get(i, []), lo, hi), busy_host)
            for i in range(run.devices)]
    return sum(idle) / len(idle) / 1e6 / run.jobs


def _innermost(spans, t: int) -> str | None:
    """The program span open at ``t`` that started last, on any thread."""
    open_ = [s for s in spans if s.start <= t < s.end]
    return max(open_, key=lambda s: s.start).name if open_ else None


def gap_labels(run, top: int = 10) -> list:
    """The longest idle gaps of the first device, as ``breakdown`` lists
    them, each named ``<bench state>/<program span>`` by the innermost
    program span open at the gap's middle (its ``label``), or the bare
    state where none is open."""
    off = offset_ns(run)
    lo, hi = run.window_ns
    if off is None or not run.trace.ops:
        return []
    spans = on_trace_clock(run.tracer_spans, off, label)
    out = []
    gaps = xtrace.gaps(run.trace.ops[min(run.trace.ops)], lo, hi)
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) // 2
        state = xtrace.host_state(run.trace.host, mid)
        name = _innermost(spans, mid)
        out.append([f"{state}/{name}" if name else state, (b - a) / 1e9])
    return out


def idle_by_span(run) -> dict:
    """All idle time of the first device, in ms per job, split by the
    innermost program span open at each instant (``none`` where no span
    is open), largest first."""
    off = offset_ns(run)
    lo, hi = run.window_ns
    if off is None or not run.trace.ops or not run.jobs:
        return {}
    spans = on_trace_clock(run.tracer_spans, off, label)
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    out: dict = {}
    for a, b in xtrace.gaps(run.trace.ops[min(run.trace.ops)], lo, hi):
        cuts = [a] + [t for t in bounds if a < t < b] + [b]
        for x, y in zip(cuts, cuts[1:]):
            name = _innermost(spans, x) or "none"
            out[name] = out.get(name, 0) + y - x
    return {k: v / 1e6 / run.jobs for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def log_gap_labels(run, top: int = 10):
    """Print ``gap_labels`` and ``idle_by_span`` on stderr, as the run's
    log shows the checks."""
    labels = gap_labels(run, top)
    if labels:
        print(f"idle gaps by program span: {labels}", file=sys.stderr, flush=True)
        print(f"idle ms per job by program span: {idle_by_span(run)}",
              file=sys.stderr, flush=True)
