"""Sorted segmented reduction — Pallas TPU kernel.

Grid (D, n_blocks): one value column at a time, sequential over row tiles
of that column; a ``(1, 128)`` VMEM tile carries the running segment value
across tiles. Each column and the boundary flags are laid out lane-dense
as ``(N/128, 128)`` (lanes.py ``lane_layout``), and the in-tile
segmented inclusive scan is lanes.py's ``scan_tile``: log-depth
``pltpu.roll`` sweeps with iota masks, no HBM intermediates. Backs
reduceByKey/groupBy of the dataflow layer (paper's TeraSort/K-Means path).

Compute dtype follows the input (f32 floats, i32 ints — the ops wrapper
normalizes): integer reductions are associative-exact, which is what lets
the shuffle engine's differential gate demand bit-identity with the jnp
oracle on the counting hot path (docs/kernels.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_reduce.lanes import LANES, lane_layout, scan_tile, tile_last, to_lanes

_FNS = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


def _kernel(v_ref, h_ref, o_ref, carry, *, op):
    fn = _FNS[op]
    i = pl.program_id(1)
    v, seen = scan_tile(v_ref[...], h_ref[...], fn)
    # the prefix before the tile's first boundary continues the previous
    # tile's segment
    v = jnp.where((i > 0) & (seen == 0), fn(carry[...], v), v)
    o_ref[...] = v
    carry[...] = tile_last(v)


def segment_reduce_fwd(values, boundaries, op: str = "sum", block: int = 8192,
                       interpret: bool = False):
    """values: (N, D) pre-masked on invalid rows; boundaries: (N,) bool =
    head-or-invalid flags. ``block`` is the number of rows per grid step
    (whole (8, 128) tiles; see ``lane_layout``). Returns the inclusive
    segmented scan (N, D), values.dtype."""
    N, D = values.shape
    rows, n_pad = lane_layout(N, block)
    # padding rows are segments of their own, past every real row
    flags = to_lanes(boundaries.astype(jnp.int32), n_pad, 1)
    cols = jax.vmap(lambda c: to_lanes(c, n_pad, 0))(values.T)  # (D, R, 128)
    R = flags.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=(D, R // rows),
        in_specs=[
            pl.BlockSpec((None, rows, LANES), lambda d, i: (d, i, 0)),
            pl.BlockSpec((rows, LANES), lambda d, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, rows, LANES), lambda d, i: (d, i, 0)),
        out_shape=jax.ShapeDtypeStruct(cols.shape, values.dtype),
        scratch_shapes=[pltpu.VMEM((1, LANES), values.dtype)],
        interpret=interpret,
        name="segment_reduce",
    )(cols, flags)
    return out.reshape(D, n_pad)[:, :N].T
