"""Flash attention forward as a Pallas TPU kernel.

TPU adaptation (DESIGN.md hardware-adaptation notes): instead of the CUDA
warp/SM decomposition, the grid iterates (batch*heads, q_blocks, kv_blocks).
The kv axis is innermost and sequential, so the online-softmax running
(max, sum) and the output accumulator live in VMEM scratch across it, and
Pallas streams one (BLOCK_KV, D) tile of K and of V per step, double
buffered.  Each step does two MXU matmuls (scores, probs x V); causal and
window masks skip kv tiles that no query of the q tile can see.

VMEM working set per program instance, independent of the sequence length:
    q tile   (BLOCK_Q, D), k/v tiles (BLOCK_KV, D) each, all double buffered
    acc      (BLOCK_Q, D) f32 + (BLOCK_Q, 1) running max/sum
    scores   (BLOCK_Q, BLOCK_KV) f32
For D=128, BLOCK_Q=256, BLOCK_KV=512 in bf16 that is about 1.3 MiB.  The
TPU compiler holds each kernel to a scoped VMEM limit of 16 MiB on v5e
(of 128 MiB physical), so K and V cannot stay resident whole: at D=128
they pass that limit from T=16384.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, s_ref, acc_ref, *,
                      scale, causal, window, block_q, block_kv, q_offset):
    """One (batch*head, q_block, kv_block) program instance."""
    qi, j = pl.program_id(1), pl.program_id(2)
    q_first = qi * block_q + q_offset
    k_first = j * block_kv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step():
        v = v_ref[...]
        sc = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bkv)
        if causal:
            shape = (block_q, block_kv)
            q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            mask = k_pos <= q_pos
            if window:
                mask &= (q_pos - k_pos) < window
            sc = jnp.where(mask, sc, NEG_INF)
        m = m_ref[...]                                      # (bq, 1)
        m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(sc - m_safe)
        corr = jnp.exp(jnp.maximum(m, -1e29) - m_safe)
        s_ref[...] = s_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    if causal:
        # skip kv tiles wholly in the future of (or, with a window, wholly
        # too far behind) every query of this q tile
        visible = k_first <= q_first + block_q - 1
        if window:
            visible &= k_first + block_kv - 1 > q_first - window
        pl.when(visible)(step)
    else:
        step()

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = (acc_ref[...] / jnp.maximum(s_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_kv: int = DEFAULT_BLOCK_KV,
                        interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,H,D); k,v: (B,T,H,D) -> (B,S,H,D).

    S must divide by block_q, T by block_kv.  Heads/batch are folded into
    the grid's first axis; the q tile stays resident while KV tiles stream.
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    assert S % block_q == 0 and T % block_kv == 0

    scale = 1.0 / math.sqrt(D)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    grid = (B * H, S // block_q, T // block_kv)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, q_offset=T - S)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
