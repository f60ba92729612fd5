"""Plain conjugate gradient on tridiag(-1, 2, -1) with Dirichlet boundaries
(``chip_smoke.cg_reference``), written in ``jax.numpy`` so that it runs on
the device after the window has closed.

The reference keeps its vectors in float64 (``jax.enable_x64``; XLA:TPU
emulates float64 in pairs of float32, far above the job's float32). The
control keeps them in bfloat16, the step below the job's float32, and
accumulates its dot products in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("iters", "dtype"))
def _norm2(b, *, iters, dtype):
    acc = jnp.float64 if dtype == "float64" else jnp.float32

    def dot(a, c):
        return jnp.sum(a.astype(acc) * c.astype(acc))

    def matvec(x):
        z = jnp.zeros((1,), x.dtype)
        return 2 * x - jnp.concatenate([z, x[:-1]]) - jnp.concatenate([x[1:], z])

    def body(_, carry):
        x, r, q, rs = carry
        Aq = matvec(q)
        alpha = rs / jnp.maximum(dot(q, Aq), 1e-30)
        x = (x + alpha * q).astype(dtype)
        r = (r - alpha * Aq).astype(dtype)
        rs_new = dot(r, r)
        q = (r + (rs_new / jnp.maximum(rs, 1e-30)) * q).astype(dtype)
        return x, r, q, rs_new

    b = b.astype(dtype)
    x, _, _, _ = jax.lax.fori_loop(0, iters, body, (jnp.zeros_like(b), b, b, dot(b, b)))
    return dot(x, x)


def norm2(b: np.ndarray, iters: int, dtype: str = "float64") -> float:
    """|x|^2 of ``iters`` CG iterations from x = 0 on A x = b, with the
    vectors kept in ``dtype`` ("float64" or "bfloat16")."""
    with jax.enable_x64(dtype == "float64"):
        return float(_norm2(jnp.asarray(b), iters=iters, dtype=dtype))
