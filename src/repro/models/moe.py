"""Mixture-of-experts MLP, dropless.

Every (token, expert) row is computed: the router picks each token's top-k
experts, the B*S*k rows are sorted by expert and run through grouped
matmuls over ragged groups (``kernels.moe_gmm.ragged_matmul``: megablox's
Pallas ``gmm`` on a TPU), then gathered back per token and combined with the
renormalised gates (``kernels.moe_combine``: a Pallas gather-reduce on a TPU
in prefill).  The named scopes ``moe.route``, ``moe.gmm`` and
``moe.combine`` mark its three parts in the device's op view, and
``moe.shared`` a shared expert.

Expert parallelism: a layer may hold a share of the experts, the bank's
``held`` experts from ``first_expert`` on.  It routes over all
``n_experts``, computes the rows routed to the experts it holds, with their
gates as routed, and neither computes nor adds the others (the chips that
hold them would); the layer that holds every expert is the uncut layer.
A shared expert, which every share computes alike, is added in full.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.moe_combine.ops import group_rows, moe_combine
from ..kernels.moe_gmm.ops import ragged_matmul
from .layers import dense_init, mlp, mlp_params


def moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype,
               held: int = 0, shared_d_ff: int = 0) -> Dict:
    """Router over ``n_experts``; banks of the ``held`` experts (0: all);
    a shared SwiGLU expert of width ``shared_d_ff`` (0: none)."""
    ks = jax.random.split(key, 4)
    def expert_bank(k, a, b):
        keys = jax.random.split(k, held or n_experts)
        return jnp.stack([dense_init(kk, a, b, dtype) for kk in keys])

    p = {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "wi_gate": expert_bank(ks[1], d_model, d_ff),
        "wi_up": expert_bank(ks[2], d_model, d_ff),
        "wo": expert_bank(ks[3], d_ff, d_model),
    }
    if shared_d_ff:
        p["shared_mlp"] = mlp_params(jax.random.fold_in(key, 4), d_model,
                                     shared_d_ff, dtype)
    return p


def moe_mlp(
    p: Dict,
    x: jnp.ndarray,                  # (B, S, D)
    *,
    n_experts: int,
    top_k: int,
    first_expert: int = 0,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (output (B,S,D), aux metrics incl. load-balance loss).

    ``aux`` counts the (token, expert) rows the experts computed
    (``moe_rows``) and the most any one expert took (``moe_max_group``);
    a layer holding a share of the experts also the rows routed to experts
    it does not hold (``moe_rows_elsewhere``), counted and not computed.
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
    out, counts = _dropless(p, x, gate_vals, expert_idx, n_experts,
                            first_expert)
    # the group sizes the grouped matmuls take: a row in no group (an
    # expert id outside [0, E)) is not computed and not counted
    aux.update(moe_rows=counts.sum(), moe_max_group=counts.max())
    if counts.shape[0] < n_experts:
        aux["moe_rows_elsewhere"] = expert_idx.size - counts.sum()
    if "shared_mlp" in p:
        with jax.named_scope("moe.shared"):
            out = out + mlp(p["shared_mlp"], x)
    return out, aux


def _dropless(p: Dict, x: jnp.ndarray, gate_vals: jnp.ndarray,
              expert_idx: jnp.ndarray, n_experts: int, first: int = 0):
    """Every (token, expert) row of the held experts through its expert:
    sort the B*S*k rows by expert, the held experts' first, grouped matmuls
    over their ragged groups, then each token's rows gathered back by their
    sorted positions and combined with the gates.  Returns (output (B,S,D),
    rows per held expert int32[held])."""
    B, S, D = x.shape
    k = expert_idx.shape[-1]
    held = p["wi_gate"].shape[0]
    with jax.named_scope("moe.route"):
        flat_e = expert_idx.reshape(B * S, k)
        if first:       # the held experts as ids 0 .. held-1
            flat_e = (flat_e - first) % n_experts
        # group sizes and sorted positions from one cumulative sum: no
        # scatter; rows routed past the held experts are in no group
        counts, pos, bounds = group_rows(flat_e, held)
        order = jnp.argsort(flat_e.reshape(-1))        # rows by expert
        xs = x.reshape(B * S, D)[order // k]           # (R, D) sorted
    with jax.named_scope("moe.gmm"):
        h = jax.nn.silu(ragged_matmul(xs, p["wi_gate"], counts)) \
            * ragged_matmul(xs, p["wi_up"], counts)
        ys = ragged_matmul(h, p["wo"], counts)         # (R, D) sorted
    with jax.named_scope("moe.combine"):
        # rows past the held experts' are in no group: not computed (the
        # grouped matmul leaves them unwritten), not read and not added
        out = moe_combine(ys, pos, gate_vals.reshape(B * S, k), bounds)
    return out.reshape(B, S, D), counts
