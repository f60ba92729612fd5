"""Public SSD scan wrapper: CPU auto-interpret + ref-vjp backward.

Also hosts ``prefix_scan`` — the SSD carry pattern applied to a 1-D
prefix scan (prefix.py, docs/kernels.md)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.prefix import prefix_scan_fwd
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_fwd


def _should_interpret():
    return jax.default_backend() != "tpu"


def prefix_scan(x, op: str = "sum", block: int = 8192, interpret=None,
                reverse: bool = False):
    """Inclusive prefix scan (sum/max/min) over a 1-D array.

    ``reverse=True`` scans from the tail (a suffix scan). Bool rides as
    i32 and is cast back.
    Bit-identical to ``prefix_scan_ref`` for integer dtypes (associative-
    exact ops — any association order agrees)."""
    interpret = _should_interpret() if interpret is None else interpret
    (N,) = x.shape
    if N == 0:
        return x
    squeeze_bool = x.dtype == jnp.bool_
    v = x.astype(jnp.int32) if squeeze_bool else x
    if reverse:
        v = v[::-1]
    out = prefix_scan_fwd(v, op=op, block=block, interpret=interpret)
    if reverse:
        out = out[::-1]
    return out.astype(bool) if squeeze_bool else out


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
