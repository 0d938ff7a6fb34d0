"""Oracle for the ragged grouped expert matmul."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ragged_ref(x, w, group_sizes):
    """x: (M, K) sorted by group; w: (G, K, N); group_sizes: int32[G]
    summing to M -> (M, N): each row times its group's weights."""
    return jax.lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32),
                              group_sizes).astype(x.dtype)
