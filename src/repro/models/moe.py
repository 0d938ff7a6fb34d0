"""Mixture-of-experts MLP with GCR-style capacity admission.

Dense-dispatch MoE in the TPU idiom (GShard/Switch style): routing produces a
(tokens, experts, capacity) dispatch tensor contracted with einsums - no
scatter/gather, fully shardable over the ``model`` axis (expert parallelism).

**GCR-MoE (beyond-paper, DESIGN.md section 2).**  Expert capacity is a saturated
shared resource; tokens are the contending "threads".  Standard dense MoE
admits tokens *by position* (FIFO) and always drops the same tail positions
when an expert saturates - the starvation problem GCR's periodic shuffling
solves for locks.  With ``gcr_moe=True`` the admission priority is rotated by
a step-dependent offset (the analogue of GCR's THRESHOLD-based promotion), so
over time every position gets a fair share of expert capacity; dropped
(passive) tokens fall through on the residual path, which is the work-
conserving fallback.

**Dropless** (``dropless=True``): no capacity, so nothing to admit.  The
B*S*k (token, expert) rows are sorted by expert and run through grouped
matmuls over ragged groups (``kernels.moe_gmm.ragged_matmul``: megablox's
Pallas ``gmm`` on a TPU), then unsorted and combined with the gates.  The
named scopes ``moe.route``, ``moe.gmm`` and ``moe.combine`` mark its three
parts in the device's op view.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

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


def _capacity(n_tokens: int, n_experts: int, top_k: int,
              capacity_factor: float) -> int:
    cap = int(n_tokens * top_k * capacity_factor / n_experts)
    return max(8, ((cap + 7) // 8) * 8)   # pad to sublane multiple


def moe_mlp(
    p: Dict,
    x: jnp.ndarray,                  # (B, S, D)
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    gcr_admission: bool = False,
    priority_offset: Optional[jnp.ndarray] = None,  # scalar int32 (step-derived)
    dropless: bool = False,
    sc=lambda x, kind=None: x,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Returns (output (B,S,D), aux metrics incl. load-balance loss).

    Capacity dispatch is *grouped per batch row* (GShard groups): admission
    ranks, capacity and the scatter/gather are computed independently per
    sequence, so under data parallelism every dispatch structure is
    device-local and only the expert computation itself crosses devices (EP
    all-to-all).  ``aux`` counts the (token, expert) rows the experts
    computed (``moe_rows``) and the most any one expert took
    (``moe_max_group``).
    """
    if dropless and gcr_admission:
        raise ValueError("a dropless MoE has no capacity to admit into; "
                         "gcr_admission needs the capacity path")
    B, S, D = x.shape

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
    if dropless:
        out, counts = _dropless(p, x, gate_vals, expert_idx, n_experts)
        # the group sizes the grouped matmuls take: a row in no group
        # (an expert id outside [0, E)) is not computed and not counted
        aux.update(moe_drop_frac=jnp.zeros((), jnp.float32),
                   moe_rows=counts.sum(), moe_max_group=counts.max())
        return out, aux

    cap = _capacity(S, n_experts, top_k, capacity_factor)

    # --- admission order (per group) --------------------------------------
    # Standard MoE admits in token order, always starving the same tail
    # positions when an expert saturates.  GCR-MoE rotates the priority
    # origin each step (the paper's periodic promotion shuffle); rotation is
    # a cyclic shift, so the "sort" by priority is a roll - no sort op.

    def ranks_of(assign_b):                                   # (S,k,E)
        flat = assign_b.reshape(S * top_k, n_experts)
        return (jnp.cumsum(flat, axis=0) - flat).reshape(S, top_k, n_experts)

    if gcr_admission and priority_offset is not None:
        off = priority_offset % S
        sort_idx = (jnp.arange(S) + off) % S       # priority order -> token
        unsort = (jnp.arange(S) - off) % S         # token -> priority order
        ranks = jax.vmap(lambda a: ranks_of(a[sort_idx])[unsort])(assign)
    else:
        ranks = jax.vmap(ranks_of)(assign)                    # (B,S,k,E)
    rank_in_expert = (ranks * assign).sum(-1)                 # (B,S,k)

    admitted = rank_in_expert < cap                           # active set
    gate_vals = gate_vals * admitted                          # passive -> 0

    # scatter dispatch (per group): copy each (token, k-slot) into its
    # expert's capacity buffer; dropped slots land in a discard row.
    flat_e = jnp.where(admitted, expert_idx, n_experts
                       ).reshape(B, S * top_k)
    flat_c = jnp.where(admitted, rank_in_expert, 0).reshape(B, S * top_k)
    x_rep = jnp.broadcast_to(x[:, :, None], (B, S, top_k, D)
                             ).reshape(B, S * top_k, D)

    def scatter_row(fe, fc, xr):
        buf = jnp.zeros((n_experts + 1, cap, D), x.dtype)
        return buf.at[fe, fc].set(xr)[:n_experts]

    expert_in = jax.vmap(scatter_row)(flat_e, flat_c, x_rep)  # (B,E,C,D)
    expert_in = sc(expert_in, "moe_buf")

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in, p["wi_gate"])) \
        * jnp.einsum("becd,edf->becf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("becf,efd->becd", h, p["wo"])     # (B,E,C,D)
    expert_out = sc(expert_out, "moe_buf")

    # gather back and combine with gate weights (per group)
    def gather_row(eo, fe_raw, fc):
        return eo[fe_raw, fc]                                 # (S*k, D)

    gathered = jax.vmap(gather_row)(
        expert_out, expert_idx.reshape(B, S * top_k), flat_c)
    gathered = gathered * gate_vals.reshape(B, S * top_k, 1).astype(x.dtype)
    out = gathered.reshape(B, S, top_k, D).sum(axis=2)

    kept = (assign * admitted[..., None]).sum(axis=(0, 1, 2))  # (E,)
    aux.update(moe_drop_frac=1.0 - admitted.astype(jnp.float32).mean(),
               moe_rows=kept.sum(), moe_max_group=kept.max())
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
