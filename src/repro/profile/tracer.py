"""JobTracer — the per-job/per-worker profiler (docs/profiling.md).

Attach to a job before running actions; export a Chrome-trace timeline
after::

    tracer = JobTracer()
    tracer.attach(job)            # spans of the job's tasks, live
    tracer.attach_worker(worker)  # metrics "profile/" mount + cost model
    ... run actions ...
    tracer.save("trace.json")     # open in chrome://tracing / Perfetto

Every span is live: the scheduler (core/job.py) opens ``lock_wait``, the
task's own span and its ``compute`` and ``settle`` phases through
``spans.span()`` as they happen, with the traced job's buffer, and every
span opened inside the task on the same thread — engine ``stage:``/
``wide:``, ``compile:``, ``fetch:``/``collect:``, ``import:``, ``native:``
— lands in the same buffer (docs/profiling.md §schema). While any tracer
is attached, the same spans go to ``jax.profiler``'s trace as
``ignis:<name>``. The tracer also feeds every finished task's duration
into its ``CostModel``'s history, which is what
``ignis.task.speculative.timeout=auto`` reads.
"""
from __future__ import annotations

import threading
import weakref

from repro.profile import spans as _spans
from repro.profile.cost import CostModel
from repro.profile.spans import Span, TraceBuffer, save_chrome, to_chrome


def task_lane(task) -> str:
    """The lane label for a task: its gang group's label (matching
    ``job.explain()``'s ``group=`` annotation), else its worker name,
    else the driver."""
    if task.group is not None:
        return task.group.label()
    if task.worker is not None:
        return task.worker.name
    return "driver"


class JobTracer:
    """Collects spans for any number of jobs/workers; one buffer, one
    timeline. Thread-safe (the scheduler completes tasks on pool threads)."""

    def __init__(self, cost_model: CostModel | None = None):
        self.buffer = TraceBuffer()
        self.cost = cost_model or CostModel()
        self._lock = threading.Lock()
        self._jobs: list = []
        self._live = None  # finalizer that takes this tracer's spans off

    # ------------------------------------------------------------------
    # attachment
    # ------------------------------------------------------------------
    def attach(self, job) -> "JobTracer":
        """Trace ``job``: its tasks open their spans into this tracer's
        buffer, and the scheduler notifies it as each task resolves
        (cost-history observation). Turns ``spans.span()`` on."""
        job.tracer = self
        with self._lock:
            self._jobs.append(job)
            if self._live is None:
                _spans.tracing(+1)
                self._live = weakref.finalize(self, _spans.tracing, -1)
        return self

    def attach_worker(self, worker) -> "JobTracer":
        """Mount ``profile/`` on ``worker``'s metrics tree and adopt the
        worker engine's cost model, so observations and decisions share
        state. The engine's spans come with the traced job's tasks."""
        if getattr(worker.engine, "cost_model", None) is not None:
            self.cost = worker.engine.cost_model
        if hasattr(worker, "mount_metrics"):
            worker.mount_metrics("profile", self.summary)
        return self

    def detach(self):
        with self._lock:
            jobs, self._jobs = self._jobs, []
            live, self._live = self._live, None
        for job in jobs:
            if job.tracer is self:
                job.tracer = None
        if live is not None:
            live()

    # ------------------------------------------------------------------
    # scheduler callback (core/job.py `_run_locked` end)
    # ------------------------------------------------------------------
    def task_done(self, task):
        """Feed the task's duration into the cost history. Called once per
        resolved task, failed or not; its spans were recorded live."""
        if not task.t_end:
            return
        key = self.task_key(task)
        if key is not None:
            self.cost.observe_task(key, task.t_end - task.t_start)

    @staticmethod
    def task_key(task):
        """The cost-history key for a task — shared with the scheduler's
        own observation path so both feed one history."""
        from repro.core.job import task_history_key

        return task_history_key(task)

    # ------------------------------------------------------------------
    # export / introspection
    # ------------------------------------------------------------------
    def spans(self) -> list[Span]:
        return self.buffer.spans()

    def to_chrome(self) -> dict:
        return to_chrome(self.buffer.spans())

    def save(self, path: str):
        save_chrome(self.buffer.spans(), path)

    def summary(self) -> dict:
        """The ``profile/`` metrics namespace: span counts and per-phase
        wall totals (milliseconds)."""
        spans = self.buffer.spans()
        task_spans = [s for s in spans if s.cat == "task" and s.name
                      not in ("compute", "settle")]
        by = lambda name: sum(s.dur for s in spans if s.name == name)
        return {
            "spans": len(spans),
            "tasks": len(task_spans),
            "engine_spans": sum(1 for s in spans if s.cat == "engine"),
            "compute_ms": by("compute") * 1e3,
            "lock_wait_ms": by("lock_wait") * 1e3,
            "settle_ms": by("settle") * 1e3,
            "makespan_ms": ((max(s.t1 for s in spans) - min(s.t0 for s in spans)) * 1e3
                            if spans else 0.0),
            "cost": self.cost.snapshot(),
        }
