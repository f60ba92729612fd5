"""Lane-dense layout and in-tile scan of the segment_reduce kernel.

A 1-D operand of N elements is padded and viewed lane-dense as
``(N/128, 128)`` in row-major order, and one grid step takes
``(rows, 128)`` with ``rows`` a multiple of 8 — whole ``(8, 128)`` vreg
tiles, which is what Mosaic lowers. The in-tile scan (``scan_tile``) is
two Hillis–Steele sweeps built from ``pltpu.roll`` plus iota masks: along
the 128 lanes of every row, then down the rows over the row totals. No
cumsum, no unaligned slices.

Integer min/max/sum are associative-exact, so any association order —
this scan's, or the plain-JAX oracle's — produces bit-identical results;
that is the property the wide-stage differential tests pin.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

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
    """Hillis–Steele inclusive segmented scan of a 2-D tile along ``axis``.
    ``f`` (int32, nonzero = a segment starts here) is scanned to "a segment
    start was seen at or before this element"."""
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    off = 1
    while off < v.shape[axis]:
        ok = idx >= off  # pltpu.roll wraps around: mask the wrapped entries
        vs = pltpu.roll(v, off, axis)
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
    """Inclusive segmented scan of a ``(rows, 128)`` tile in row-major order.

    ``f`` holds int32 segment-start flags (a flagged element restarts the
    running value). Returns ``(scanned, seen)`` where ``seen`` is nonzero
    from the first flagged element of the tile on."""
    v, f = _sweep(v, f, fn, 1)  # along the lanes of every row
    # row totals (last lane), broadcast along the lanes, scanned down the rows
    tv = jnp.broadcast_to(pltpu.roll(v, 1, 1)[:, 0:1], v.shape)
    tf = jnp.broadcast_to(pltpu.roll(f, 1, 1)[:, 0:1], f.shape)
    tv, tf = _sweep(tv, tf, fn, 0)
    # fold in the total of all previous rows (exclusive: shift one row down)
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    pv = pltpu.roll(tv, 1, 0)
    pf = pltpu.roll(tf, 1, 0)
    v = jnp.where((row >= 1) & (f == 0), fn(pv, v), v)
    return v, jnp.where(row >= 1, f | pf, f)
