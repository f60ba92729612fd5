"""Public segment_reduce wrappers: masking, CPU auto-interpret.

``segment_reduce`` is the standalone inclusive-scan entry (kernel tests);
``segment_totals`` is the shuffle-stage ABI (docs/kernels.md): the drop-in
kernel implementation of core/shuffle.segmented_reduce, combining the
segment scan with the ssd-carry prefix pass for the last-row gather.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.ref import heads_of
from repro.kernels.segment_reduce.segment_reduce import segment_reduce_fwd
from repro.kernels.ssd_scan.ops import prefix_scan
from repro.kernels.ssd_scan.prefix import op_identity


def _should_interpret():
    return jax.default_backend() != "tpu"


def _compute_dtype(dtype):
    """f32 for floats, i32 for ints/bool — the kernel's native dtypes."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.float32
    return jnp.int32


def _scan(keys, valid, values, op, mask_value, block, interpret):
    """Shared core: mask invalid rows to ``mask_value``, run the
    segmented-scan kernel. Returns (heads, scanned (N, D) in the compute
    dtype, squeeze)."""
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    ct = _compute_dtype(v.dtype)
    heads = heads_of(keys, valid)
    hb = heads | ~valid
    v = jnp.where(valid[:, None], v.astype(ct), jnp.asarray(mask_value, ct))
    out = segment_reduce_fwd(v, hb, op=op, block=block, interpret=interpret)
    return heads, out, squeeze


def segment_reduce(keys, valid, values, op: str = "sum", block: int = 8192,
                   interpret=None):
    """Inclusive segmented scan over sorted-key runs.

    keys: (N,) sorted; valid: (N,); values: (N,) or (N, D).
    Returns (heads (N,), scanned (N, …)) — same contract as the ref;
    float inputs compute in f32, integer/bool inputs exactly in i32.
    """
    interpret = _should_interpret() if interpret is None else interpret
    ct = _compute_dtype(values.dtype)
    heads, out, squeeze = _scan(keys, valid, values, op,
                                op_identity(op, ct), block, interpret)
    return heads, (out[:, 0] if squeeze else out)


def segment_totals(keys, valid, values, op: str, identity, block: int = 8192,
                   interpret=None):
    """Shuffle-stage ABI: per-segment totals broadcast to every row.

    Drop-in for core/shuffle.segmented_reduce with a builtin fn: invalid
    rows are masked to the *user* identity (the oracle's contract — the
    identity never enters a combine, invalid rows are their own
    boundaries), the segment scan runs in the kernel, and the last-row
    gather uses the prefix kernel's reverse cummin. Bit-identical to the
    oracle for associative-exact data (integers; max/min on any dtype).

    Returns (heads (N,) bool, totals (N, …) in values.dtype).
    """
    interpret = _should_interpret() if interpret is None else interpret
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros(0, bool), values
    heads, scanned, squeeze = _scan(keys, valid, values, op, identity,
                                    block, interpret)
    hb = heads | ~valid
    # last row of each segment = (next boundary) - 1, via the suffix-min
    # prefix pass (core/shuffle.segmented_reduce's exact formula)
    idx = jnp.arange(n)
    head_pos = jnp.where(hb, idx, n).astype(jnp.int32)
    suff_min = prefix_scan(head_pos, op="min", block=block,
                           interpret=interpret, reverse=True)
    nxt = jnp.concatenate([suff_min[1:], jnp.full((1,), n, jnp.int32)])
    last_pos = jnp.clip(jnp.where(nxt >= n, n - 1, nxt - 1), 0, n - 1)
    out = scanned[last_pos].astype(values.dtype)
    return heads, (out[:, 0] if squeeze else out)
