"""Decode attention over a stacked, lane-dense K/V ring as a Pallas TPU
kernel.

The ring is (N, B, T, C): N rings (a model's layers) of B sequences, T
slots, C = kv_heads * head_dim lanes.  The kernel reads ring ``index``
straight from that stack (the index is a scalar prefetched for the block
index maps), so no layer's ring is first copied out of it.  The grid is
(B, T // block_t); the slot axis is innermost and sequential, so the online
softmax's running max and sum and the output accumulator stay in VMEM
across it while Pallas streams one (block_t, C) tile of K and of V per step,
double buffered (the last tile masked where it reads past T).

Query rows arrive block-diagonal over the lanes (row r of KV head h holds
its query in h's head_dim lanes and zeros elsewhere), so each step is two
plain MXU matmuls over all C lanes, (R, C) x (block_t, C)^T and
(R, block_t) x (block_t, C); the products outside a row's head are exact
zeros, and the caller keeps each row's own head lanes of the result.

The step's new tokens are not in the ring yet (the caller writes them after
the layer scan): their scores and values stand in for those of their slots.

VMEM per instance: K and V tiles (block_t, C) double buffered, the (R,
block_t) validity tile, the (R, C) query, output and float32 accumulator.
For granite-moe-1b-a400m (C = 512) and qwen3-0.6b (C = 1024) at a 1156-slot
ring read whole that is about 4.8 and 9.5 MiB, under v5e's 16 MiB scoped
default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TILE_BYTES = 5 << 19     # 2.5 MiB: one K or V tile at most (four are live)


def block_slots(T: int, C: int, itemsize: int) -> int:
    """Slots per K/V tile: T whole where it fits ``TILE_BYTES``, else the
    most whole 128-slot lane tiles that do; the last tile may then hang
    past T."""
    fit = TILE_BYTES // (C * itemsize)
    return T if T <= fit else max(128, fit // 128 * 128)


def _kernel(index_ref, slots_ref, q_ref, k_ref, v_ref, valid_ref, *rest,
            scale, block_t, n_slots, n_new):
    if n_new:
        nk_ref, nv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                              # (R, C)
    s = jax.lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = t * block_t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    qf = q.astype(jnp.float32)
    for j in range(n_new):      # a new token's score in place of its slot's
        s_new = jnp.sum(qf * nk_ref[j:j + 1, :].astype(jnp.float32),
                        axis=1, keepdims=True) * scale
        s = jnp.where(col == slots_ref[j], s_new, s)
    valid = valid_ref[...] != 0
    v = v_ref[...]
    if n_slots % block_t:       # the last tile reads past the ring: unset
        valid &= col < n_slots
        row = t * block_t + jax.lax.broadcasted_iota(jnp.int32, (block_t, 1),
                                                     0)
        v = jnp.where(row < n_slots, v, jnp.zeros_like(v))
    s = jnp.where(valid, s, NEG_INF)

    m = m_ref[...]                                              # (R, 1)
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    m_safe = jnp.maximum(m_new, -1e29)      # a row with no valid slot yet
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.maximum(m, -1e29) - m_safe)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    extra = jnp.zeros(acc_ref.shape, jnp.float32)
    for j in range(n_new):      # and its value in place of its slot's
        hit = col == slots_ref[j]
        p_j = jnp.sum(jnp.where(hit, p, 0.0), axis=1, keepdims=True)
        p = jnp.where(hit, 0.0, p)
        extra = extra + p_j * nv_ref[j:j + 1, :].astype(jnp.float32)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv + extra
    m_ref[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ring_decode_fwd(q, ring_k, ring_v, index, valid, new_k, new_v, slots, *,
                    scale: float, interpret: bool = False):
    """q: (B, R, C); ring_k, ring_v: (N, B, T, C); index: int32 scalar;
    valid: (R, T) int32; new_k, new_v: (B, n, C) or None; slots: int32[n]
    -> (B, R, C) in q's dtype."""
    B, R, C = q.shape
    T = ring_k.shape[2]
    bt = block_slots(T, C, ring_k.dtype.itemsize)
    n_new = 0 if new_k is None else new_k.shape[1]
    if slots is None:
        slots = jnp.full((1,), -1, jnp.int32)
    index = jnp.reshape(index, (1,)).astype(jnp.int32)

    ring_spec = pl.BlockSpec((None, None, bt, C),
                             lambda b, t, i, s: (i[0], b, t, 0))
    row_spec = pl.BlockSpec((None, R, C), lambda b, t, i, s: (b, 0, 0))
    in_specs = [row_spec, ring_spec, ring_spec,
                pl.BlockSpec((R, bt), lambda b, t, i, s: (0, t))]
    args = [q, ring_k, ring_v, valid.astype(jnp.int32)]
    if n_new:
        new_spec = pl.BlockSpec((None, n_new, C), lambda b, t, i, s: (b, 0, 0))
        in_specs += [new_spec, new_spec]
        args += [new_k, new_v]

    kernel = functools.partial(_kernel, scale=scale, block_t=bt, n_slots=T,
                               n_new=n_new)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pl.cdiv(T, bt)),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, C), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, R, C), q.dtype),
        interpret=interpret,
    )(index, slots.astype(jnp.int32), *args)
