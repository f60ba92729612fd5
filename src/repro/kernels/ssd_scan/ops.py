"""Public SSD scan wrapper: CPU auto-interpret + ref-vjp backward."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_fwd


def _should_interpret():
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_scan(x, dt, A_log, Bm, Cm, chunk, interpret=None):
    interpret = _should_interpret() if interpret is None else interpret
    return ssd_scan_fwd(x, dt, A_log, Bm, Cm, chunk, interpret=interpret)


def _fwd(x, dt, A_log, Bm, Cm, chunk, interpret):
    out = ssd_scan(x, dt, A_log, Bm, Cm, chunk, interpret)
    return out, (x, dt, A_log, Bm, Cm)


def _bwd(chunk, interpret, res, g):
    x, dt, A_log, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: ssd_ref(*a, chunk), x, dt, A_log, Bm, Cm)
    return vjp(g)


ssd_scan.defvjp(_fwd, _bwd)
