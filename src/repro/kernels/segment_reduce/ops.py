"""Public segment_reduce wrappers: masking, CPU auto-interpret.

``segment_reduce`` is the standalone inclusive-scan entry (kernel tests);
``segment_totals`` is the shuffle-stage ABI (docs/kernels.md): the drop-in
kernel implementation of core/shuffle.segmented_reduce, which keeps each
segment's total on its last row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.shuffle import segment_tails
from repro.kernels.segment_reduce.lanes import op_identity
from repro.kernels.segment_reduce.ref import heads_of
from repro.kernels.segment_reduce.segment_reduce import segment_reduce_fwd


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
    if keys.shape[0] == 0:
        return jnp.zeros(0, bool), values.astype(ct)
    heads, out, squeeze = _scan(keys, valid, values, op,
                                op_identity(op, ct), block, interpret)
    return heads, (out[:, 0] if squeeze else out)


def segment_totals(keys, valid, values, op: str, identity, block: int = 8192,
                   interpret=None):
    """Shuffle-stage ABI: the segmented scan, each total on its last row.

    Drop-in for core/shuffle.segmented_reduce with a builtin fn: invalid
    rows are masked to the *user* identity (the oracle's contract — the
    identity never enters a combine, invalid rows are their own
    boundaries) and the segment scan runs in the kernel. The total is
    exact at each kept row (``tails``, the last valid row of every
    segment); other valid rows hold a partial total, invalid rows the
    identity. No gather: the kept value is the scanned element itself.
    Bit-identical to the oracle for associative-exact data (integers;
    max/min on any dtype).

    Returns (tails (N,) bool, scanned (N, …) in values.dtype).
    """
    interpret = _should_interpret() if interpret is None else interpret
    if keys.shape[0] == 0:
        return jnp.zeros(0, bool), values
    _, scanned, squeeze = _scan(keys, valid, values, op, identity, block,
                                interpret)
    out = scanned.astype(values.dtype)
    return segment_tails(keys, valid), (out[:, 0] if squeeze else out)
