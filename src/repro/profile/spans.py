"""Trace spans and the Chrome-trace exporter (docs/profiling.md §schema).

A ``Span`` is one closed interval of wall time on one thread: a task body,
its lock wait, its collective settle, or an engine-level stage/node
compute. ``TraceBuffer`` collects spans thread-safely and renders the
Chrome trace event format (the ``chrome://tracing`` / Perfetto JSON
schema: complete ``"X"`` events with microsecond ``ts``/``dur``, thread
metadata ``"M"`` events).

Threads, not lanes, are the nesting domain: after a settle hands a task's
lock off (core/job.py ``_settle``), the *next* task on the same lane
overlaps the first task's collective await — so same-lane spans may
interleave, while same-thread spans always nest. The exporter therefore
keys ``tid`` on the executing thread and carries the lane/gang label in
``args["lane"]``, which is what the schema tests validate
(tests/test_profile.py).

``span()`` is the one way the program opens a span (docs/profiling.md
§schema). While a ``JobTracer`` is attached it opens
``jax.profiler.TraceAnnotation("ignis:" + name)`` — so under
``jax.profiler`` the span lands in the trace beside the device ops, on the
same clock — and records the same interval in the ``TraceBuffer`` of the
traced job whose task runs on this thread. Buffer spans are stamped with
``clock()``, the clock the profiler's host events use (CLOCK_REALTIME): a
buffer span and its ``ignis:`` copy in the trace differ by the profile's
start time only. While no tracer is attached, ``span()`` reads one module
flag and returns a shared no-op context: it creates no span object and
makes no call into JAX.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from dataclasses import dataclass, field

import jax

PREFIX = "ignis:"

_NOOP = contextlib.nullcontext()
_on = False  # a JobTracer is attached somewhere in the process
_tracers = 0
_state_lock = threading.Lock()
_tls = threading.local()  # .buffer: where this thread's spans go


def clock() -> float:
    """Seconds on the profiler's host clock (CLOCK_REALTIME, which TSL's
    ``TraceMe`` stamps)."""
    return time.time_ns() / 1e9


def tracing(delta: int):
    """Count attached tracers up or down (``JobTracer``); spans are live
    while the count is positive."""
    global _tracers, _on
    with _state_lock:
        _tracers = max(0, _tracers + delta)
        _on = _tracers > 0


def span(name: str, cat: str = "engine", label: str | None = None,
         into: "TraceBuffer | None" = None, **args):
    """A context manager timing ``name`` (docs/profiling.md §schema).

    The profiler sees ``ignis:<name>`` with ``args``; the buffer records
    ``label`` (default ``name``) under ``cat``. ``into`` names the buffer,
    and nested spans on this thread inherit it. Without ``into`` a span
    goes where the enclosing span on this thread went, and nowhere outside
    any."""
    if not _on:
        return _NOOP
    buf = into if into is not None else getattr(_tls, "buffer", None)
    return _NOOP if buf is None else _Live(buf, name, cat, label, args)


def first_call(site: str, fn):
    """``fn`` as a jit cache hands it out on a miss. While tracing is on,
    its first call (the trace and the compile) runs inside
    ``span("compile:" + site)``; otherwise ``fn`` itself."""
    if not _on:
        return fn
    pending = [True]

    def call(*a, **kw):
        if pending:
            pending.clear()
            with span("compile:" + site):
                return fn(*a, **kw)
        return fn(*a, **kw)

    return call


class _Live:
    """One open span: a profiler annotation and its buffer copy."""

    __slots__ = ("buf", "name", "cat", "label", "args", "_ann", "_t0", "_prev")

    def __init__(self, buf, name, cat, label, args):
        self.buf, self.name, self.cat, self.label = buf, name, cat, label
        self.args = args  # the buffer copy reads them at exit

    def __enter__(self):
        self._prev = getattr(_tls, "buffer", None)
        _tls.buffer = self.buf
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.name, **self.args)
        self._ann.__enter__()
        self._t0 = clock()
        return self

    def __exit__(self, *exc):
        t1 = clock()
        self._ann.__exit__(*exc)
        _tls.buffer = self._prev
        self.buf.add(Span(self.label or self.name, self.cat, self._t0, t1,
                          threading.get_ident(), dict(self.args)))
        return False


@dataclass(frozen=True)
class Span:
    name: str      # "compute", "lock_wait", "settle", "stage:...", ...
    cat: str       # "task" | "engine" | "sched"
    t0: float      # seconds on clock()
    t1: float
    tid: int       # executing thread id
    args: dict = field(default_factory=dict)  # lane, kind, attempt, ...

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class TraceBuffer:
    """Append-only, thread-safe span store for one tracer."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[Span] = []

    def add(self, span: Span):
        with self._lock:
            self._spans.append(span)

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def __len__(self):
        with self._lock:
            return len(self._spans)

    def clear(self):
        with self._lock:
            self._spans.clear()


def to_chrome(spans: list[Span], process_name: str = "ignis") -> dict:
    """Render spans as a Chrome trace JSON object.

    ``ts``/``dur`` are microseconds relative to the earliest span (Chrome
    renders absolute clock values poorly); every distinct tid gets
    a ``thread_name`` metadata event naming the lanes it ran."""
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    epoch = min(s.t0 for s in spans)
    events: list[dict] = [{
        "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
        "args": {"name": process_name},
    }]
    lanes_by_tid: dict[int, set] = {}
    for s in spans:
        lanes_by_tid.setdefault(s.tid, set()).add(s.args.get("lane", "driver"))
    for tid, lanes in sorted(lanes_by_tid.items()):
        events.append({
            "name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
            "args": {"name": "worker [" + ", ".join(sorted(lanes)) + "]"},
        })
    for s in sorted(spans, key=lambda s: (s.t0, -s.t1)):
        events.append({
            "name": s.name, "cat": s.cat, "ph": "X", "pid": 0, "tid": s.tid,
            "ts": round((s.t0 - epoch) * 1e6, 3),
            "dur": round(max(0.0, s.dur) * 1e6, 3),
            "args": dict(s.args),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def save_chrome(spans: list[Span], path: str, process_name: str = "ignis"):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(to_chrome(spans, process_name), f)


def validate(trace: dict) -> list[str]:
    """Schema violations in a Chrome trace object: malformed events,
    negative durations, same-thread spans that overlap without nesting.
    Empty list = valid. Used by tests and the bench harness — an exported
    timeline that Chrome renders misleadingly should fail loudly here."""
    problems: list[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    by_tid: dict[int, list[dict]] = {}
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph == "M":
            continue
        if ph != "X":
            problems.append(f"event {i}: unexpected ph {ph!r}")
            continue
        for k in ("name", "ts", "dur", "tid", "pid"):
            if k not in e:
                problems.append(f"event {i}: missing {k!r}")
        if e.get("dur", 0) < 0:
            problems.append(f"event {i} ({e.get('name')}): negative dur")
        if e.get("ts", 0) < 0:
            problems.append(f"event {i} ({e.get('name')}): negative ts")
        by_tid.setdefault(e.get("tid", 0), []).append(e)
    for tid, evs in by_tid.items():
        evs = sorted(evs, key=lambda e: (e["ts"], -(e["ts"] + e["dur"])))
        stack: list[tuple] = []  # (end, name)
        for e in evs:
            t0, t1 = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1][0] <= t0 + 1e-9:
                stack.pop()
            if stack and t1 > stack[-1][0] + 1e-6:
                problems.append(
                    f"tid {tid}: {e['name']!r} [{t0},{t1}] overlaps "
                    f"{stack[-1][1]!r} (ends {stack[-1][0]}) without nesting")
            stack.append((t1, e["name"]))
    return problems
