"""Oracle: fused MoE routing = softmax → top-k → renorm → capacity ordinals.

Ordinal semantics match models/moe.moe_ffn: assignments are ranked within
their expert in flattened (token-major, slot-minor) order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_route_ref(logits, k: int, capacity: int):
    """logits: (T, E). Returns (weights (T,k) f32, idx (T,k) i32,
    pos (T,k) i32 ordinal-within-expert, keep (T,k) bool)."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    oh = jax.nn.one_hot(idx.reshape(-1), E, dtype=jnp.int32)  # (T·k, E)
    csum = jnp.cumsum(oh, axis=0)
    pos = ((csum - oh) * oh).sum(-1).reshape(T, k)
    keep = pos < capacity
    return w, idx.astype(jnp.int32), pos.astype(jnp.int32), keep
