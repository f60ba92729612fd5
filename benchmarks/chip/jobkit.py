"""What every job kind shares: its workers, the benchmark's host spans, the
checks it reports, and a cache of the jitted digest programs that the jobs'
native apps run.

A job kind is a module ``jobs/<kind>.py`` with a class ``Job(JobBase)``. The
harness calls, in this order: ``setup()``; ``run_one()`` for every warm-up
and timed job, each returning the job's answer as the user receives it;
``release()`` once the window has closed; then ``check(answers)``, which
builds the plain reference from the seed and compares every answer with it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import PartitionSpec as P


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def span(name: str):
    """A benchmark host span (``bench:<name>``) in the profiler's trace."""
    return jax.profiler.TraceAnnotation("bench:" + name)


class JobBase:
    #: input records of one job (for ``records_per_s``)
    records: int = 0
    #: what ``check`` found wrong, for the run's log
    detail: str = ""

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.executors = int(traffic["executors"])
        if self.executors != chips:
            raise ValueError(f"traffic asks for {self.executors} executors "
                             f"but the cell has {chips} chips")
        #: a ``repro.profile.tracer.JobTracer`` in the traced run, else None
        self.tracer = None
        self.workers: list = []
        self._cluster = None
        self._digests: dict = {}

    # ---- workers ---------------------------------------------------------
    def cluster(self):
        from repro.core import ICluster, IProperties

        if self._cluster is None:
            self._cluster = ICluster(IProperties(
                {"ignis.executor.instances": str(self.executors)}))
        return self._cluster

    def worker(self, kind: str = "dataflow"):
        from repro.core import IWorker

        w = IWorker(self.cluster(), kind)
        self.workers.append(w)
        return w

    def counters(self) -> dict:
        """The workers' counters, summed by path (``shuffle/bytes_moved``)."""
        out: dict = {}
        for w in self.workers:
            for ns in ("shuffle", "kernels", "stages"):
                for k, v in w.metrics(ns).items():
                    if isinstance(v, (int, float)):
                        out[f"{ns}/{k}"] = out.get(f"{ns}/{k}", 0) + v
        return out

    def traced(self, job):
        """Attach the traced run's tracer to an ``IJob``."""
        if self.tracer is not None:
            self.tracer.attach(job)
        return job

    # ---- per-shard digest programs (run inside the jobs' native apps) -----
    def digest(self, ctx, local, *args):
        """``local(*shard_args) -> (k,)`` run on every shard of the app's
        communicator, stacked to (executors, k); jitted once per mesh."""
        mesh, axis = ctx.comm()
        key = (local, mesh, axis)
        fn = self._digests.get(key)
        if fn is None:
            from repro.core import compat

            fn = jax.jit(compat.shard_map(
                lambda *a: local(*a)[None], mesh=mesh,
                in_specs=tuple(P(axis) for _ in args), out_specs=P(axis)))
            self._digests[key] = fn
        return fn(*args)

    # ---- the harness's protocol ------------------------------------------
    def setup(self):
        raise NotImplementedError

    def run_one(self):
        raise NotImplementedError

    def check(self, answers: list) -> list:
        raise NotImplementedError

    #: per-kernel call shapes for the roofline readers: name -> dict
    kernel_shapes: dict = {}

    def release(self):
        """Drop every frame and worker, so the reference finds the device
        memory free."""
        self.workers.clear()
        self._cluster = None
        self._digests.clear()
        for k in [k for k, v in vars(self).items()
                  if type(v).__module__.startswith("repro.")]:
            setattr(self, k, None)
