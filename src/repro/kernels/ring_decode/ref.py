"""Oracle for ``ring_decode``: the ring read whole, the new tokens put in
their slots, one softmax over the valid slots (float32 scores)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_decode_ref(q, ring_k, ring_v, index, valid, new_k=None, new_v=None,
                    slots=None, *, scale: float):
    k, v = ring_k[index], ring_v[index]                  # (B, T, C)
    if new_k is not None:
        t = jnp.arange(k.shape[1])[None, :, None]
        for j in range(new_k.shape[1]):
            k = jnp.where(t == slots[j], new_k[:, j:j + 1].astype(k.dtype), k)
            v = jnp.where(t == slots[j], new_v[:, j:j + 1].astype(v.dtype), v)
    s = jnp.einsum("brc,btc->brt", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None] != 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("brt,btc->brc", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
