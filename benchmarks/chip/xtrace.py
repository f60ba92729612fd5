"""Reduction of a JAX profiler trace (``.xplane.pb``) to the intervals the
per-layer metrics read.

A device plane (``/device:TPU:<i>``) holds a line of XLA operations and a
line of XLA modules (whole executables). The benchmark's own host spans are
``jax.profiler.TraceAnnotation``s whose names start with ``bench:``; they
lie on the host plane, on the same clock. Everything here works on plain
``(name, start_ns, end_ns)`` records, so it is tested on a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
BENCH_PREFIX = "bench:"


@dataclass
class Span:
    name: str
    start: int  # ns
    end: int  # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclass
class Trace:
    """Device ops and modules per device id, and the benchmark's host spans."""

    ops: dict = field(default_factory=dict)  # device id -> [Span]
    modules: dict = field(default_factory=dict)  # device id -> [Span]
    host: list = field(default_factory=list)  # [Span] named bench:*

    def window(self) -> Span | None:
        """The measured window: the ``bench:window`` host span."""
        spans = [s for s in self.host if s.name == BENCH_PREFIX + "window"]
        return spans[0] if spans else None


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    """Read the device and benchmark spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    tr = Trace()
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                dest = tr.ops if line.name == OPS_LINE else tr.modules
                spans = dest.setdefault(int(m.group(2)), [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    spans.append(Span(ev.name, s, s + int(ev.duration_ns)))
            elif m is None:
                for ev in line.events:
                    if ev.name.startswith(BENCH_PREFIX):
                        s = int(ev.start_ns)
                        tr.host.append(Span(ev.name, s, s + int(ev.duration_ns)))
    for d in (tr.ops, tr.modules):
        for spans in d.values():
            spans.sort(key=lambda s: s.start)
    tr.host.sort(key=lambda s: s.start)
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def clip(spans, lo: int, hi: int) -> list:
    """The parts of ``spans`` inside [lo, hi)."""
    out = []
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            out.append(Span(s.name, a, b))
    return out


def merged(spans) -> list:
    """The union of ``spans`` as disjoint ``(start, end)`` pairs, in order."""
    out: list = []
    for s in sorted(spans, key=lambda s: s.start):
        if out and s.start <= out[-1][1]:
            if s.end > out[-1][1]:
                out[-1][1] = s.end
        else:
            out.append([s.start, s.end])
    return [(a, b) for a, b in out]


def busy_ns(spans, lo: int, hi: int) -> int:
    """Length of the union of ``spans`` inside [lo, hi)."""
    return sum(b - a for a, b in merged(clip(spans, lo, hi)))


def gaps(spans, lo: int, hi: int) -> list:
    """The idle intervals of [lo, hi) that no span covers, as ``(start, end)``."""
    out, t = [], lo
    for a, b in merged(clip(spans, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def time_by_name(spans, lo: int, hi: int, match) -> int:
    """Summed duration inside [lo, hi) of the spans whose name ``match``es."""
    return sum(s.dur for s in clip(spans, lo, hi) if match(s.name))


_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")


def op_family(name: str) -> str:
    """An op's instruction name without its numeric suffix. The trace names
    an op by its HLO text, ``%fusion.12 = s32[...] fusion(...)``, or by the
    instruction alone, ``fusion.12``: both give ``fusion``."""
    m = _INSTR.match(name.strip())
    return m.group(1) if m else name


def host_state(host, t: int) -> str:
    """The innermost benchmark span around instant ``t`` (the last to start
    among those that cover it), without its prefix, or ``other``."""
    best = None
    for s in host:
        if s.start <= t < s.end and s.name != BENCH_PREFIX + "window":
            if best is None or s.start >= best.start:
                best = s
    return best.name[len(BENCH_PREFIX):] if best is not None else "other"


def breakdown(tr: Trace, lo: int, hi: int, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices, by op family)
    and the longest idle gaps of device 0 (or the first device), each named
    by what the benchmark's host thread was doing at the gap's middle."""
    by: dict = {}
    for spans in tr.ops.values():
        for s in clip(spans, lo, hi):
            f = op_family(s.name)
            by[f] = by.get(f, 0) + s.dur
    ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    dev = min(tr.ops) if tr.ops else None
    idle = []
    if dev is not None:
        for a, b in sorted(gaps(tr.ops[dev], lo, hi), key=lambda g: g[0] - g[1])[:top]:
            idle.append([host_state(tr.host, (a + b) // 2), (b - a) / 1e9])
    return {"device_ops": [[k, v / 1e9] for k, v in ops], "idle_gaps": idle}


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------


def idle_share(run) -> float | None:
    """Idle % of the traced window, averaged over the cell's devices."""
    lo, hi = run.window_ns
    if run.trace is None or hi <= lo or not run.trace.ops:
        return None
    busy = [busy_ns(run.trace.ops.get(i, []), lo, hi) for i in range(run.devices)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))


def op_ms_per_job(run, match, line: str = "ops") -> float | None:
    """Device milliseconds per job of the ops (or modules) whose name
    ``match``es, averaged over the cell's devices; None where none ran."""
    lo, hi = run.window_ns
    if run.trace is None or hi <= lo or not run.jobs:
        return None
    spans = getattr(run.trace, line)
    per_dev = [time_by_name(spans.get(i, []), lo, hi, match) for i in range(run.devices)]
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / 1e6 / run.jobs
