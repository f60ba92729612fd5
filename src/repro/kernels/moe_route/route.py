"""Shuffle bucket routing — Pallas TPU kernel (docs/kernels.md).

The MoE router's capacity-ordinal technique (moe_route.py) applied to the
shuffle engine's exchange: rows are "tokens", destination executors are
"experts", bucket capacity C is the expert capacity. Grid (n_blocks,)
sequential over lane-dense ``(rows, 128)`` tiles of the destinations
(ssd_scan/prefix.py ``lane_layout``). The per-destination running counts
live in the ``(p, 1, 128)`` counts output, which stays resident in VMEM
across the grid, so ordinals are globally consistent in row order without
an argsort. Per tile and destination: a ``scan_tile`` prefix count of the
rows routed there gives the in-tile ordinal, and the carried count gives
the base. ``p`` is the executor count, so the loop over destinations is
short.

Ordinals are exact integers — for row r with destination b, ``pos`` is the
number of earlier rows routed to b, which is precisely the rank a stable
argsort-by-destination assigns (core/shuffle._pack_exchange). That makes
the kernel-routed packed buffer bit-identical to the argsort path: kept
rows land in the same unique slots; only the sliced-off overflow scratch
slot can differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ssd_scan.prefix import LANES, lane_layout, scan_tile, tile_last, to_lanes


def _kernel(d_ref, pos_ref, keep_ref, cnt_ref, *, p, capacity):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    d = d_ref[...]  # (rows, 128) int32 in [0, p); == p marks padding rows
    pos = jnp.zeros_like(d)
    for b in range(p):
        hit = d == b
        seen, _ = scan_tile(hit.astype(jnp.int32), None, jnp.add)
        base = cnt_ref[b]  # (1, 128): rows routed to b by earlier tiles
        pos = jnp.where(hit, base + seen - 1, pos)
        cnt_ref[b] = base + tile_last(seen)
    pos_ref[...] = pos
    keep_ref[...] = ((pos < capacity) & (d < p)).astype(jnp.int32)


def bucket_route_fwd(dest, p: int, capacity: int, block: int = 8192,
                     interpret: bool = False):
    """dest: (N,) int32 in [0, p). ``block`` is the number of rows per grid
    step (whole (8, 128) tiles; see ``lane_layout``). Returns (pos (N,)
    i32, keep (N,) bool, counts (p,) i32 — final per-destination demand)."""
    (N,) = dest.shape
    rows, n_pad = lane_layout(N, block)
    # the sentinel p matches no destination: padding neither claims
    # ordinals nor inflates counts
    d = to_lanes(dest.astype(jnp.int32), n_pad, p)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    pos, keep, counts = pl.pallas_call(
        functools.partial(_kernel, p=p, capacity=capacity),
        grid=(d.shape[0] // rows,),
        in_specs=[spec],
        out_specs=[spec, spec,
                   pl.BlockSpec((p, 1, LANES), lambda i: (0, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(d.shape, jnp.int32),
            jax.ShapeDtypeStruct(d.shape, jnp.int32),
            jax.ShapeDtypeStruct((p, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="bucket_route",
    )(d)
    return (pos.reshape(n_pad)[:N], keep.reshape(n_pad)[:N].astype(bool),
            counts[:, 0, 0])
