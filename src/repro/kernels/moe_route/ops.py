"""Public routing wrappers: CPU auto-interpret (and padding for moe_route)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_route.moe_route import moe_route_fwd


def _should_interpret():
    return jax.default_backend() != "tpu"


def bucket_route(dest, p: int, capacity: int, block: int = 8192, interpret=None):
    """Shuffle-exchange routing (route.py): capacity ordinals in row order.

    dest: (N,) int32 in [0, p). Returns (pos (N,) i32, keep (N,) bool,
    counts (p,) i32) — bit-identical to the stable-argsort formulation in
    core/shuffle._pack_exchange (and to ``bucket_route_ref``)."""
    from repro.kernels.moe_route.route import bucket_route_fwd

    interpret = _should_interpret() if interpret is None else interpret
    (N,) = dest.shape
    if N == 0:
        return (jnp.zeros(0, jnp.int32), jnp.zeros(0, bool),
                jnp.zeros(p, jnp.int32))
    return bucket_route_fwd(dest, p=p, capacity=capacity, block=block,
                            interpret=interpret)


def moe_route(logits, k: int, capacity: int, block_t: int = 256, interpret=None):
    interpret = _should_interpret() if interpret is None else interpret
    T = logits.shape[0]
    pad = (-T) % block_t if T > block_t else 0
    x = logits
    if pad:
        # padded tokens route somewhere but their ordinals come AFTER all real
        # tokens only if appended — they are appended, so real ordinals are
        # unaffected; padded outputs are sliced off.
        x = jnp.concatenate([x, jnp.full((pad, x.shape[1]), -1e9, x.dtype)])
    w, idx, pos, keep = moe_route_fwd(x, k, capacity, block_t=block_t,
                                      interpret=interpret)
    return w[:T], idx[:T], pos[:T], keep[:T]
