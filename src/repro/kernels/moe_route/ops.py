"""Public MoE routing wrapper: CPU auto-interpret and padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_route.moe_route import moe_route_fwd


def _should_interpret():
    return jax.default_backend() != "tpu"


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
