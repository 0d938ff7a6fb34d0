"""Oracle for the grouped expert matmul."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_ref(x, w):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F) per-expert matmuls."""
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def ragged_ref(x, w, group_sizes):
    """x: (M, K) sorted by group; w: (G, K, N); group_sizes: int32[G]
    summing to M -> (M, N): each row times its group's weights."""
    return jax.lax.ragged_dot(x.astype(jnp.float32), w.astype(jnp.float32),
                              group_sizes).astype(x.dtype)
