"""Oracle for ``moe_combine``: each token's k rows gathered by position,
weighted by their float32 gates, summed in float32, rounded once."""

from __future__ import annotations

import jax.numpy as jnp


def moe_combine_ref(ys, pos, gates):
    """ys: (R, D) rows sorted by expert; pos: (T, k) int32, the row of each
    (token, slot) in ``ys``, -1 for none; gates: (T, k) float32 -> (T, D) in
    ys's dtype."""
    y = ys[jnp.maximum(pos, 0)].astype(jnp.float32)              # (T, k, D)
    y = jnp.where((pos >= 0)[..., None], y, 0.0)
    return (y * gates[..., None]).sum(axis=1).astype(ys.dtype)
