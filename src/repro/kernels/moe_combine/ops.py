"""Public ops of the dropless MoE's row bookkeeping and combine.

``group_rows`` places every (token, slot) row of a routing in the
expert-sorted order without a scatter: one cumulative sum over the tokens
gives each expert's group size (what the grouped matmuls take), each row's
position (the inverse of the stable sort by expert) and the kernel's slab
table.  ``moe_combine`` sums each token's rows with their gates: the Pallas
kernel on a TPU where the tokens fill a tile (prefill), the oracle's gather
elsewhere (decode, and any backend but a TPU).  The kernel's gradients are
plain XLA, so a training step can differentiate through it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import interpret_mode
from .kernel import CHUNK, ROW_ALIGN, TILE, moe_combine_fwd
from .ref import moe_combine_ref


def group_rows(experts, groups: int):
    """experts: (T, k) int32, the expert of each (token, slot), each token
    naming an expert at most once; ids outside [0, groups) are in no group.

    Returns ``counts`` int32[groups], the rows of each group; ``pos`` int32
    (T, k), the place of each row in the rows sorted stably by expert (the
    groups' rows first, in order), -1 for a row in no group; ``bounds``
    int32 (ceil(T / TILE) + 1, groups): ``bounds[i, e]`` is the place of the
    first row that token tile i sends to group e, ``bounds[-1]`` each group's
    end."""
    hot = experts[..., None] == jnp.arange(groups, dtype=experts.dtype)
    per_token = hot.sum(axis=1, dtype=jnp.int32)                  # (T, G)
    before = jnp.cumsum(per_token, axis=0) - per_token  # earlier tokens
    counts = before[-1] + per_token[-1]
    first = jnp.cumsum(counts) - counts                 # each group's start
    at = first + before                                           # (T, G)
    pos = jnp.where(hot.any(axis=-1),
                    jnp.sum(jnp.where(hot, at[:, None, :], 0), axis=-1), -1)
    bounds = jnp.concatenate([at[::TILE], (first + counts)[None]])
    return counts, pos.astype(jnp.int32), bounds


def moe_combine(ys, pos, gates, bounds, *, impl: str = "auto"):
    """ys: (T * k, D) expert rows sorted by expert, computed up to the
    groups' total (rows past it may hold anything); pos, bounds:
    ``group_rows``'s; gates: (T, k) float32 -> (T, D) in ys's dtype: each
    token's rows times their gates, summed in float32 (rows in no group add
    nothing).

    impl: ``auto`` is the kernel on a TPU for bfloat16 rows where T fills a
    tile, else the oracle; ``pallas`` | ``interpret`` force the kernel,
    ``ref`` the oracle."""
    T = pos.shape[0]
    if impl == "auto":
        fits = T >= TILE and ys.dtype == jnp.bfloat16
        impl = "pallas" if fits and jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return moe_combine_ref(ys, pos, gates)
    return _combine(ys, pos, gates, bounds, interpret_mode(impl))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(ys, pos, gates, bounds, interpret):
    """The kernel on whole token tiles and whole row tiles."""
    T = pos.shape[0]
    pad = -T % TILE
    if pad:                     # whole tiles; the padded tokens have no rows
        pos = jnp.pad(pos, ((0, pad), (0, 0)), constant_values=-1)
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
    R = ys.shape[0]
    rows = max(-(-R // ROW_ALIGN) * ROW_ALIGN, CHUNK)
    if rows > R:                # whole HBM row tiles, one chunk at least
        ys = jnp.pad(ys, ((0, rows - R), (0, 0)))
    out = moe_combine_fwd(ys, pos, gates, bounds, interpret=interpret)
    return out[:T] if pad else out


def _combine_fwd(ys, pos, gates, bounds, interpret):
    return _combine(ys, pos, gates, bounds, interpret), (ys, pos, gates)


def _combine_bwd(interpret, res, dout):
    """The oracle's gradients, in XLA.  d ys: each sorted row's gate times
    its token's output cotangent, gathered in sorted order (the inverse of
    ``pos`` is one sort), zero for rows in no group; d gates: each row
    dotted with its token's cotangent."""
    ys, pos, gates = res
    R, k = ys.shape[0], pos.shape[1]
    flat = pos.reshape(-1)
    key = jnp.where(flat >= 0, flat, R)
    src = jnp.argsort(key)                  # the (token, slot) of each row
    hit = (key[src] == jnp.arange(R))[:, None]
    dy = gates.reshape(-1)[src, None] * dout[src // k].astype(jnp.float32)
    d_ys = jnp.where(hit, dy, 0.0).astype(ys.dtype)
    d_gates = jnp.einsum("tkd,td->tk", ys[jnp.maximum(pos, 0)], dout,
                         preferred_element_type=jnp.float32)
    d_gates = jnp.where(pos >= 0, d_gates, 0.0).astype(gates.dtype)
    return d_ys, None, d_gates, None


_combine.defvjp(_combine_fwd, _combine_bwd)
