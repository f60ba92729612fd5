"""The hybrid job of the IgnisHPC paper's Fig. 12, as ``chip_smoke.job``.

One job is one ``IJob`` of async branches over two workers of one cluster:
dataflow ``map`` prepares a right-hand side b = 2u + 1 from resident
uniforms, ``import_data`` hands it to the SPMD worker's native ``cg_app``
(CG on tridiag(-1, 2, -1)), and the solution comes back as a frame that
dataflow reduces to |x|^2. Beside it, in the same job: ``count`` of the
right-hand side and ``count_by_value`` of resident Zipf keys. The answer is
(|x|^2, rows, key counts). The reference solves the same b with a plain CG
in float64 on the device (``refcg``) and counts the keys with
``np.bincount``.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.chip import gen, refcg
from benchmarks.chip.jobkit import Check, JobBase, span

U_STREAM, KEY_STREAM = 1, 2


def _rhs(v):
    return v * 2.0 + 1.0


def _square(v):
    return v * v


def _add(a, c):
    return a + c


class Job(JobBase):
    def setup(self):
        c = self.cfg
        self.records = int(c["n"])
        self.df = self.worker("dataflow")
        self.spmd = self.worker("spmd")
        self.spmd.load_library("repro.apps.stencil")
        self.u = self.df.parallelize(self.uniforms())
        self.keys = self.df.parallelize(self.zipf())

    def uniforms(self):
        return gen.uniform(self.seed, int(self.cfg["n"]), stream=U_STREAM)

    def zipf(self):
        c = self.cfg
        return gen.zipf_ids(self.seed, int(c["n"]), int(c["vocab"]),
                            float(c["zipf_s"]), stream=KEY_STREAM)

    def run_one(self):
        from repro.core import Ignis

        with span("build"):
            rhs = self.u.map(_rhs)
            x = self.spmd.call("cg_app", self.spmd.import_data(rhs),
                               iters=int(self.cfg["iters"]))
            job = self.traced(Ignis.job("fig12-cg"))
        with span("submit"):
            f_norm = x.map(_square).reduce_async(_add, 0.0, job=job)
            f_rows = rhs.count_async(job=job)
            f_hist = self.keys.count_by_value_async(job=job)
        with span("wait"):
            answer = (float(f_norm.result()), int(f_rows.result()), f_hist.result())
        failed = job.metrics("tasks")["failed"]
        job.release()
        if failed:
            raise RuntimeError(f"fig12 job: {failed} tasks failed")
        return answer

    # ---- reference -------------------------------------------------------
    def _b(self) -> np.ndarray:
        u = np.asarray(jax.device_get(self.uniforms()))
        return u * np.float32(2.0) + np.float32(1.0)

    def _hist(self) -> np.ndarray:
        return np.bincount(np.asarray(jax.device_get(self.zipf())),
                           minlength=int(self.cfg["vocab"]))

    def control_answer(self):
        """The reference with its CG vectors kept in bfloat16."""
        norm = refcg.norm2(self._b(), int(self.cfg["iters"]), "bfloat16")
        hist = self._hist()
        return (norm, int(self.cfg["n"]),
                {int(k): int(v) for k, v in enumerate(hist) if v})

    def check(self, answers):
        norm = refcg.norm2(self._b(), int(self.cfg["iters"]))
        hist = self._hist()
        n, vocab = int(self.cfg["n"]), int(self.cfg["vocab"])
        errs, wrong = [], 0
        for got_norm, rows, counts in answers:
            errs.append(abs(got_norm - norm) / norm)
            dense = np.zeros(vocab, np.int64)
            ok = all(0 <= k < vocab for k in counts)
            if ok:
                dense[list(counts)] = list(counts.values())
            if not ok or rows != n or not np.array_equal(dense, hist):
                wrong += 1
        if wrong:
            self.detail = f"{wrong} answers with wrong rows or key counts"
        return [Check("norm_rel_err", max(errs) if errs else float("inf"),
                      self.cfg["limits"]["norm_rel_err"]),
                Check("wrong_answers", wrong, 0)]
