"""Mixture-of-experts MLP, dropless.

Every (token, expert) row is computed: the router picks each token's top-k
experts, the B*S*k rows are sorted by expert and run through grouped
matmuls over ragged groups (``kernels.moe_gmm.ragged_matmul``: megablox's
Pallas ``gmm`` on a TPU), then unsorted and combined with the renormalised
gates.  The named scopes ``moe.route``, ``moe.gmm`` and ``moe.combine`` mark
its three parts in the device's op view.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_gmm.ops import ragged_matmul
from .layers import dense_init


def moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    def expert_bank(k, a, b):
        keys = jax.random.split(k, n_experts)
        return jnp.stack([dense_init(kk, a, b, dtype) for kk in keys])

    return {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "wi_gate": expert_bank(ks[1], d_model, d_ff),
        "wi_up": expert_bank(ks[2], d_model, d_ff),
        "wo": expert_bank(ks[3], d_ff, d_model),
    }


def moe_mlp(
    p: Dict,
    x: jnp.ndarray,                  # (B, S, D)
    *,
    n_experts: int,
    top_k: int,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (output (B,S,D), aux metrics incl. load-balance loss).

    ``aux`` counts the (token, expert) rows the experts computed
    (``moe_rows``) and the most any one expert took (``moe_max_group``).
    """
    with jax.named_scope("moe.route"):
        # float32 routing, at full precision: a TPU's default runs a
        # float32 matmul in bfloat16 passes, and a rounded router logit
        # can swap the k-th and (k+1)-th experts
        logits = jnp.dot(x.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)  # (B, S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (B, S, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9)
    assign = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # (B,S,k,E)
    # aux: load-balance loss (Switch) + router z-loss
    density = assign.astype(jnp.float32).mean(axis=(0, 1, 2)) * n_experts
    router_prob = probs.mean(axis=(0, 1)) * n_experts
    aux = {"moe_lb_loss": jnp.mean(density * router_prob),
           "moe_z_loss": jnp.mean(jnp.square(
               jax.nn.logsumexp(logits, axis=-1)))}
    out, counts = _dropless(p, x, gate_vals, expert_idx, n_experts)
    # the group sizes the grouped matmuls take: a row in no group (an
    # expert id outside [0, E)) is not computed and not counted
    aux.update(moe_rows=counts.sum(), moe_max_group=counts.max())
    return out, aux


def _dropless(p: Dict, x: jnp.ndarray, gate_vals: jnp.ndarray,
              expert_idx: jnp.ndarray, n_experts: int):
    """Every (token, expert) row through its expert: sort the B*S*k rows
    by expert, grouped matmuls over the ragged groups, unsort, combine with
    the gates.  Returns (output (B,S,D), rows per expert int32[E])."""
    B, S, D = x.shape
    k = expert_idx.shape[-1]
    R = B * S * k
    with jax.named_scope("moe.route"):
        flat_e = expert_idx.reshape(R)
        order = jnp.argsort(flat_e)                    # rows by expert
        counts = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)
        xs = x.reshape(B * S, D)[order // k]           # (R, D) sorted
    with jax.named_scope("moe.gmm"):
        h = jax.nn.silu(ragged_matmul(xs, p["wi_gate"], counts)) \
            * ragged_matmul(xs, p["wi_up"], counts)
        ys = ragged_matmul(h, p["wo"], counts)         # (R, D) sorted
    with jax.named_scope("moe.combine"):
        unsort = jnp.zeros((R,), jnp.int32).at[order].set(
            jnp.arange(R, dtype=jnp.int32))
        y = ys[unsort].reshape(B, S, k, D).astype(jnp.float32)
        out = (y * gate_vals[..., None]).sum(axis=2).astype(x.dtype)
    return out, counts
