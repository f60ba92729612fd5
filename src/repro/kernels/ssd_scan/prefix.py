"""Unsegmented prefix scan (cumsum/cummin/cummax) — Pallas TPU kernel.

The SSD chunk scan's inter-chunk recurrence pattern applied to the shuffle
engine's prefix pass: grid (n_blocks,) sequential over row tiles, a
``(1, 128)`` VMEM tile carries the running reduction across grid steps
(exactly how ssd_scan.py carries its (P, N) state). No wide stage calls
it: ``segment_totals`` keeps each total on its segment's last row, so the
reverse cummin it ran to find that row is gone — docs/kernels.md.

Layout (shared with the segment and route kernels): a 1-D operand of N
elements is padded and viewed lane-dense as ``(N/128, 128)`` in row-major
order, and one grid step takes ``(rows, 128)`` with ``rows`` a multiple of
8 — whole ``(8, 128)`` vreg tiles, which is what Mosaic lowers. The
in-tile scan (``scan_tile``) is two Hillis–Steele sweeps built from
``pltpu.roll`` plus iota masks: along the 128 lanes of every row, then
down the rows over the row totals. No cumsum, no unaligned slices.

Integer min/max/sum are associative-exact, so any association order —
this kernel's, or lax.cummin's — produces bit-identical results; that is
the property the wide-stage differential tests pin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FNS = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}

LANES = 128
SUBLANES = 8
TILE = LANES * SUBLANES  # elements in one (8, 128) vreg tile


def op_identity(op: str, dtype):
    """True identity of ``op`` on ``dtype`` (python scalar, static)."""
    if op == "sum":
        return 0
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return float("-inf") if op == "max" else float("inf")
    info = jnp.iinfo(jnp.dtype(dtype))
    return info.min if op == "max" else info.max


def lane_layout(n: int, block: int) -> tuple[int, int]:
    """``(rows per grid step, padded length)`` for ``n`` elements viewed as
    ``(rows, 128)``. ``block`` (elements) is rounded up to whole ``(8, 128)``
    tiles, and shrunk to the data when the data is smaller."""
    rows = SUBLANES * max(1, -(-int(block) // TILE))
    rows = min(rows, SUBLANES * max(1, -(-n // TILE)))
    step = rows * LANES
    return rows, -(-n // step) * step


def to_lanes(x, n_pad: int, fill):
    """Pad a 1-D array to ``n_pad`` with ``fill`` and view it ``(n_pad/128, 128)``."""
    pad = n_pad - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
    return x.reshape(n_pad // LANES, LANES)


def _sweep(v, f, fn, axis: int):
    """Hillis–Steele inclusive (segmented) scan of a 2-D tile along ``axis``.
    ``f`` (int32, nonzero = a segment starts here, or None) is scanned to
    "a segment start was seen at or before this element"."""
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    off = 1
    while off < v.shape[axis]:
        ok = idx >= off  # pltpu.roll wraps around: mask the wrapped entries
        vs = pltpu.roll(v, off, axis)
        if f is None:
            v = jnp.where(ok, fn(vs, v), v)
        else:
            fs = pltpu.roll(f, off, axis)
            v = jnp.where(ok & (f == 0), fn(vs, v), v)
            f = jnp.where(ok, f | fs, f)
        off *= 2
    return v, f


def tile_last(x):
    """The last element (row-major) of a 2-D tile, broadcast to ``(1, 128)``."""
    tail = pltpu.roll(pltpu.roll(x, 1, 0), 1, 1)[0:1, 0:1]
    return jnp.broadcast_to(tail, (1, LANES))


def scan_tile(v, f, fn):
    """Inclusive scan of a ``(rows, 128)`` tile in row-major order.

    ``f`` is None for a plain scan, or int32 segment-start flags for a
    segmented one (a flagged element restarts the running value). Returns
    ``(scanned, seen)`` where ``seen`` is nonzero from the first flagged
    element of the tile on (None for a plain scan)."""
    v, f = _sweep(v, f, fn, 1)  # along the lanes of every row
    # row totals (last lane), broadcast along the lanes, scanned down the rows
    tv = jnp.broadcast_to(pltpu.roll(v, 1, 1)[:, 0:1], v.shape)
    tf = None if f is None else jnp.broadcast_to(pltpu.roll(f, 1, 1)[:, 0:1], f.shape)
    tv, tf = _sweep(tv, tf, fn, 0)
    # fold in the total of all previous rows (exclusive: shift one row down)
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    pv = pltpu.roll(tv, 1, 0)
    if f is None:
        return jnp.where(row >= 1, fn(pv, v), v), None
    pf = pltpu.roll(tf, 1, 0)
    v = jnp.where((row >= 1) & (f == 0), fn(pv, v), v)
    return v, jnp.where(row >= 1, f | pf, f)


def _kernel(x_ref, o_ref, carry, *, op):
    fn = _FNS[op]
    i = pl.program_id(0)
    v, _ = scan_tile(x_ref[...], None, fn)
    v = jnp.where(i > 0, fn(carry[...], v), v)  # reduction of all previous tiles
    o_ref[...] = v
    carry[...] = tile_last(v)


def prefix_scan_fwd(x, op: str = "sum", block: int = 8192, interpret: bool = False):
    """x: (N,) int32/float32. Returns the inclusive scan (N,), same dtype.
    ``block`` is the number of elements per grid step (whole (8, 128)
    tiles; see ``lane_layout``)."""
    (N,) = x.shape
    rows, n_pad = lane_layout(N, block)
    xl = to_lanes(x, n_pad, 0)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(xl.shape[0] // rows,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xl.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), x.dtype)],
        interpret=interpret,
        name="prefix_scan",
    )(xl)
    return out.reshape(n_pad)[:N]
