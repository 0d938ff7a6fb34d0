"""Shared neural-net layers (pure JAX, functional, shard-friendly).

Conventions:
* params are plain nested dicts of jnp arrays; every function takes the
  relevant sub-dict explicitly.
* activations flow as (batch, seq, ...); attention works in grouped-query
  layout (B, S, n_kv, group, d_head) so GQA never materializes repeated
  KV heads.
* long sequences use a chunked online-softmax attention (flash-style dataflow
  expressed in XLA: lax.scan over KV chunks carrying running max/sum), so the
  32k-prefill cells compile without materializing S x S score matrices.  The
  Pallas kernel in ``repro.kernels.flash_attention`` is the TPU-optimized
  version of the same dataflow.
* KV caches are ring buffers, lane-dense (B, T, n_kv*d_head) (see
  ``multihead_attention``): full-attention archs size them at max_len,
  sliding-window archs at the window, which is what makes mixtral's
  long_500k decode cell feasible.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ring_decode.ops import ring_decode

# Chunk sizes for the chunked-attention scan (tuned for VMEM-sized tiles).
Q_CHUNK = 512
KV_CHUNK = 1024
# Use plain (materialized-scores) attention below this sequence length.
CHUNKED_ATTN_THRESHOLD = 2048

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02
            ).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def rms_norm_init(dim: int, dtype) -> jnp.ndarray:
    return jnp.ones((dim,), dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads..., d_head); positions: (..., seq) int32."""
    half = x.shape[-1] // 2
    freqs = rope_frequencies(x.shape[-1], theta)          # (half,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    # broadcast over head axes between seq and d_head
    extra = x.ndim - angles.ndim - 1
    for _ in range(extra):
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_params(key, d_model: int, n_heads: int, n_kv: int,
                     d_head: int, qk_norm: bool, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * d_head, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * d_head, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * d_head, dtype),
        "wo": dense_init(ks[3], n_heads * d_head, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = rms_norm_init(d_head, dtype)
        p["k_norm"] = rms_norm_init(d_head, dtype)
    return p


def _plain_attention(q, k, v, q_pos, k_pos, window: int,
                     causal: bool = True) -> jnp.ndarray:
    """Flat-head attention.  q: (B,S,H,D); k,v: (B,T,H,D); int32 positions.

    The flat H layout keeps attention shardable by a single mesh axis
    (GQA KV heads are repeated up to H by the caller - "kv replication")."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    mask = (k_pos >= 0)[None, :]                            # unwritten slots
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _grouped_decode_attention(q, ring_k, ring_v, index, new_k, new_v,
                              cache_pos, window: int, causal: bool = True):
    """Decode attention over a stacked, lane-dense K/V ring, without KV
    repetition.

    q: (B,S,Hkv,G,D); ring_k, ring_v: (N,B,T,Hkv*D), of which ring ``index``
    is read, each KV head's D lanes side by side in the minor dimension
    (``init_cache``); new_k, new_v: (B,S,Hkv*D), the step's tokens, which
    the ring reads as if written into their slots (None: a read-only cache,
    cross-attention).  q is placed block-diagonally over the Hkv*D lanes
    (head h's D lanes hold its queries, the others zeros), so one contraction
    over all the lanes gives every head's scores with no relayout of the
    ring; the extra products are exact zeros, and each head keeps its own D
    lanes of the value contraction.  ``ring_decode`` reads the ring from the
    stack by index (a Pallas kernel on a TPU)."""
    B, S, Hkv, G, D = q.shape
    T = ring_k.shape[2]
    eye = jnp.eye(Hkv, dtype=q.dtype)[:, None, :, None]    # (h, 1, h', 1)
    qb = (q[..., None, :] * eye).reshape(B, S * Hkv * G, Hkv * D)
    if causal:
        k_pos = _cache_slot_positions(T, cache_pos, S)
        q_pos = cache_pos + jnp.arange(S)
        valid = (k_pos[None, :] <= q_pos[:, None]) & (k_pos >= 0)[None, :]
        if window:
            valid &= (q_pos[:, None] - k_pos[None, :]) < window
    else:
        valid = jnp.ones((S, T), bool)
    valid = jnp.repeat(valid, Hkv * G, axis=0)             # rows (s, h, g)
    slots = None
    if new_k is not None:
        news = _ring_slots(T, cache_pos, S)
        js = [j for j, _ in news]
        slots = jnp.stack([jnp.asarray(slot, jnp.int32) for _, slot in news])
        new_k, new_v = new_k[:, js], new_v[:, js]
    out = ring_decode(qb, ring_k, ring_v, index, valid, new_k, new_v, slots,
                      scale=1.0 / math.sqrt(D))
    return (out.reshape(B, S, Hkv, G, Hkv, D) * eye).sum(axis=4)


def _chunk_mask(qp_blk, kp_blk, window: int, causal: bool):
    mask = (kp_blk >= 0)[None, :]
    if causal:
        mask = mask & (kp_blk[None, :] <= qp_blk[:, None])
        if window:
            mask = mask & ((qp_blk[:, None] - kp_blk[None, :]) < window)
    return mask


def _flash_fwd_impl(q, k, v, q_pos, k_pos, window: int, causal: bool):
    """Online-softmax forward.  Returns (out (B,S,H,D), lse (B,H,S))."""
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    q_chunks = max(1, S // Q_CHUNK)
    kv_chunks = max(1, T // KV_CHUNK)
    qc, kc = S // q_chunks, T // kv_chunks

    qr = q.reshape(B, q_chunks, qc, H, D).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(q_chunks, qc)
    kr = k.reshape(B, kv_chunks, kc, H, D).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, kv_chunks, kc, H, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(kv_chunks, kc)

    def per_q_chunk(args):
        q_blk, qp_blk = args
        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        s0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, qc, H, D), jnp.float32)

        def step(carry, kv_blk):
            m, s, acc = carry
            k_blk, v_blk, kp_blk = kv_blk
            sc = jnp.einsum("bshd,bthd->bhst", q_blk, k_blk
                            ).astype(jnp.float32) * scale
            mask = _chunk_mask(qp_blk, kp_blk, window, causal)
            sc = jnp.where(mask[None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            m_safe = jnp.maximum(m_new, -1e29)   # fully-masked row guard
            p = jnp.exp(sc - m_safe[..., None])
            corr = jnp.exp(jnp.maximum(m, -1e29) - m_safe)
            s_new = s * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhst,bthd->bshd", p.astype(q.dtype), v_blk
                            ).astype(jnp.float32)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
            return (m_new, s_new, acc_new), None

        (m, s, acc), _ = jax.lax.scan(step, (m0, s0, a0), (kr, vr, kp))
        denom = jnp.maximum(s, 1e-30)
        out = (acc / denom.transpose(0, 2, 1)[..., None]).astype(q.dtype)
        lse = jnp.maximum(m, -1e29) + jnp.log(denom)      # (B,H,qc)
        return out, lse

    out, lse = jax.lax.map(per_q_chunk, (qr, qp))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)
    lse = lse.transpose(1, 2, 0, 3).reshape(B, H, S)
    return out, lse


def _flash_bwd_impl(q, k, v, q_pos, k_pos, out, lse, dout,
                    window: int, causal: bool):
    """Flash backward: recompute probabilities chunk-by-chunk; the full
    (S, T) score matrix is never resident (the scan-VJP of the naive
    chunked form would save it - 4 GiB/device/layer at 4k)."""
    B, S, H, D = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    q_chunks = max(1, S // Q_CHUNK)
    kv_chunks = max(1, T // KV_CHUNK)
    qc, kc = S // q_chunks, T // kv_chunks
    f32 = jnp.float32

    # delta_i = rowsum(dO_i * O_i)   (B,H,S)
    delta = jnp.einsum("bshd,bshd->bhs", dout.astype(f32), out.astype(f32))

    qr = q.reshape(B, q_chunks, qc, H, D).transpose(1, 0, 2, 3, 4)
    qp = q_pos.reshape(q_chunks, qc)
    kr = k.reshape(B, kv_chunks, kc, H, D).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, kv_chunks, kc, H, D).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(kv_chunks, kc)
    dor = dout.reshape(B, q_chunks, qc, H, D).transpose(1, 0, 2, 3, 4)
    lser = lse.reshape(B, H, q_chunks, qc).transpose(2, 0, 1, 3)
    dlr = delta.reshape(B, H, q_chunks, qc).transpose(2, 0, 1, 3)

    def p_block(q_blk, k_blk, qp_blk, kp_blk, lse_blk):
        sc = jnp.einsum("bshd,bthd->bhst", q_blk, k_blk
                        ).astype(f32) * scale
        mask = _chunk_mask(qp_blk, kp_blk, window, causal)
        sc = jnp.where(mask[None, None], sc, NEG_INF)
        return jnp.exp(sc - lse_blk[..., None])          # (B,H,qc,kc)

    # --- dq: map over q chunks, scan kv chunks -------------------------------
    def dq_chunk(args):
        q_blk, qp_blk, do_blk, lse_blk, dl_blk = args

        def step(dq_acc, kv_blk):
            k_blk, v_blk, kp_blk = kv_blk
            p = p_block(q_blk, k_blk, qp_blk, kp_blk, lse_blk)
            dp = jnp.einsum("bshd,bthd->bhst", do_blk, v_blk).astype(f32)
            ds = p * (dp - dl_blk[..., None])
            dq_acc = dq_acc + jnp.einsum(
                "bhst,bthd->bshd", ds.astype(q.dtype), k_blk
            ).astype(f32) * scale
            return dq_acc, None

        dq0 = jnp.zeros((B, qc, H, D), f32)
        dq_blk, _ = jax.lax.scan(step, dq0, (kr, vr, kp))
        return dq_blk

    dq = jax.lax.map(dq_chunk, (qr, qp, dor, lser, dlr))
    dq = dq.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D).astype(q.dtype)

    # --- dk/dv: map over kv chunks, scan q chunks ------------------------------
    def dkv_chunk(args):
        k_blk, v_blk, kp_blk = args

        def step(carry, q_blk_all):
            dk_acc, dv_acc = carry
            q_blk, qp_blk, do_blk, lse_blk, dl_blk = q_blk_all
            p = p_block(q_blk, k_blk, qp_blk, kp_blk, lse_blk)
            dv_acc = dv_acc + jnp.einsum(
                "bhst,bshd->bthd", p.astype(q.dtype), do_blk).astype(f32)
            dp = jnp.einsum("bshd,bthd->bhst", do_blk, v_blk).astype(f32)
            ds = p * (dp - dl_blk[..., None])
            dk_acc = dk_acc + jnp.einsum(
                "bhst,bshd->bthd", ds.astype(q.dtype), q_blk
            ).astype(f32) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros((B, kc, H, D), f32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            step, (z, z), (qr, qp, dor, lser, dlr))
        return dk_blk, dv_blk

    dk, dv = jax.lax.map(dkv_chunk, (kr, vr, kp))
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, T, H, D).astype(k.dtype)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, T, H, D).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _fa(q, k, v, q_pos, k_pos, window: int, causal: bool):
    out, _ = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, causal)
    return out


def _fa_fwd(q, k, v, q_pos, k_pos, window, causal):
    out, lse = _flash_fwd_impl(q, k, v, q_pos, k_pos, window, causal)
    return out, (q, k, v, q_pos, k_pos, out, lse)


def _fa_bwd(window, causal, res, dout):
    q, k, v, q_pos, k_pos, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, q_pos, k_pos, out, lse, dout,
                                 window, causal)
    zero_pos = np.zeros(q_pos.shape, jax.dtypes.float0)
    zero_kpos = np.zeros(k_pos.shape, jax.dtypes.float0)
    return dq, dk, dv, zero_pos, zero_kpos


_fa.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, q_pos, k_pos, window: int = 0,
                    causal: bool = True) -> jnp.ndarray:
    """Memory-efficient attention with a flash-style custom VJP.

    Flat-head layout (B,S,H,D) / (B,T,H,D).  Forward saves only
    (q,k,v,out,lse); backward recomputes score chunks, so the full (S,T)
    score matrix is never resident in either pass.  This is the XLA
    reference implementation of ``repro.kernels.flash_attention``."""
    return _fa(q, k, v, q_pos, k_pos, int(window), bool(causal))


def _cache_write(cache: Dict, k: jnp.ndarray, v: jnp.ndarray,
                 cache_pos) -> Dict:
    """Write the last min(S, Tc) tokens of k/v (B,S,kv,dh) into the ring
    buffers (B,Tc,kv*dh)."""
    Tc = cache["k"].shape[1]
    B, S = k.shape[:2]
    Lw = min(S, Tc)
    slots = (cache_pos + S - Lw + jnp.arange(Lw)) % Tc

    def write(ring, new):
        new = new[:, -Lw:].reshape(B, Lw, -1).astype(ring.dtype)
        return ring.at[:, slots].set(new)

    return {"k": write(cache["k"], k), "v": write(cache["v"], v)}


def _ring_slots(Tc: int, cache_pos, S: int):
    """(j, slot) for each of the last min(S, Tc) of S tokens written from
    ``cache_pos`` on: the token's index and the ring slot it lands in."""
    return [(j, (cache_pos + j) % Tc) for j in range(S - min(S, Tc), S)]


def ring_update(buf: jnp.ndarray, new: jnp.ndarray, cache_pos,
                axis: int, first=0) -> jnp.ndarray:
    """Write the tokens of ``new`` into the ring buffer ``buf`` (its ring
    along ``axis``, ``new``'s tokens along the same axis, its leading axis
    placed from ``first`` on) as ``_cache_write`` does, one
    ``dynamic_update_slice`` per token: on a donated ``buf`` XLA writes in
    place, touching the new tokens only."""
    start = [first] + [0] * (buf.ndim - 1)
    for j, slot in _ring_slots(buf.shape[axis], cache_pos, new.shape[axis]):
        start[axis] = slot
        tok = jax.lax.slice_in_dim(new, j, j + 1, axis=axis)
        buf = jax.lax.dynamic_update_slice(buf, tok.astype(buf.dtype), start)
    return buf


def _cache_slot_positions(Tc: int, cache_pos, S: int) -> jnp.ndarray:
    """Absolute position held by ring slot i after writing S tokens:
    p(i) = last - ((last - i) mod Tc), last = cache_pos + S - 1; -1 if
    the slot has never been written."""
    last = cache_pos + S - 1
    idx = jnp.arange(Tc)
    k_pos = last - ((last - idx) % Tc)
    return jnp.where(k_pos <= last, k_pos, -1)


def multihead_attention(
    p: Dict,
    x: jnp.ndarray,                 # (B, S, d_model)
    positions: jnp.ndarray,         # (S,) absolute positions of x
    kv_src: Optional[jnp.ndarray],  # cross-attn source or None (self)
    cache: Optional[Dict],          # {"k","v"} rings (see Modes) or None
    cache_pos,                      # scalar: tokens already in cache
    *,
    n_heads: int,
    n_kv: int,
    d_head: int,
    qk_norm: bool = False,
    rope_theta: Optional[float] = 1e4,  # None: no RoPE (NoPE attention)
    window: int = 0,
    causal: bool = True,
    decode: bool = False,           # True: attend over the cache (S small)
    is_cross: bool = False,         # cross-attention (kv from encoder/cache)
    eps: float = 1e-5,
    q_scale: Optional[float] = None,  # q times this after RoPE (None: 1)
    sc=lambda x, kind=None: x,      # sharding-constraint hook
) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Returns (output (B,S,d_model), cache out): the updated cache, or in
    self-attention decode the new tokens' {"k","v"} (B,S,n_kv*d_head), which
    the caller writes into the ring (``ring_update``), and in
    cross-attention decode None (that cache is read only).

    A cache holds K and V lane-dense, (B, T, n_kv*d_head): the KV heads and
    the head dim merged into the minor dimension, which is a multiple of the
    TPU's 128 lanes in every configuration.  One position is then a
    contiguous [B, n_kv*d_head] slab of whole tiles, so decode writes a token
    without touching the rest of the ring.  (With a head dim of 64 in the
    minor place XLA would lay the ring out T-minor and pad T to the lanes,
    and a token's write would stride across the whole stack.)

    Every path scales scores by 1/sqrt(d_head); a ``q_scale`` of
    ``m * sqrt(d_head)`` makes that a score scale of ``m``.

    Modes:
      train   - cache is None: attend within the sequence.
      prefill - cache given, decode=False: attend within the sequence,
                write the last min(S, cache_len) tokens into the ring.
      decode  - cache given, decode=True: the stacked rings
                {"k","v"} (N,B,T,n_kv*d_head) and "index", the ring to
                read (a layer's); attend over the whole ring as it reads
                with the current token(s) written, and return those
                tokens' k/v, not a new ring.
    """
    B, S, _ = x.shape
    G = n_heads // n_kv
    if decode:
        # grouped layout (no KV repetition): the cache stays kv-width and is
        # sharded along its sequence dim (distributed flash-decode).
        q = (x @ p["wq"]).reshape(B, S, n_kv, G, d_head)
    else:
        # flat-head layout: shardable by a single mesh axis over H
        q = (x @ p["wq"]).reshape(B, S, n_heads, d_head)

    if is_cross and decode:
        # cross-attention decode: k/v live in the (static) cross cache
        k = v = new_cache = None
    else:
        src = x if kv_src is None else kv_src
        Tsrc = src.shape[1]
        k = (src @ p["wk"]).reshape(B, Tsrc, n_kv, d_head)
        v = (src @ p["wv"]).reshape(B, Tsrc, n_kv, d_head)

        if qk_norm:
            q = rms_norm(q, p["q_norm"], eps)
            k = rms_norm(k, p["k_norm"], eps)

        use_rope = not is_cross  # RoPE only for self-attention
        if use_rope and rope_theta is not None:
            q = apply_rope(q, positions, rope_theta)
            src_pos = positions if not decode else (
                cache_pos + jnp.arange(Tsrc))
            k = apply_rope(k, src_pos, rope_theta)
        if q_scale is not None:
            q = q * jnp.asarray(q_scale, q.dtype)

        if decode:
            # the ring is read with the new tokens in their slots, never
            # written here: the caller writes them
            k = k.reshape(B, S, n_kv * d_head)
            v = v.reshape(B, S, n_kv * d_head)
            new_cache = {"k": k, "v": v}
        elif cache is not None:
            new_cache = _cache_write(cache, k, v, cache_pos)
            # prefill: attend within the sequence
            k_full, v_full = k, v
            k_pos = positions[:Tsrc]
            q_pos = positions
        else:
            new_cache = None
            k_full, v_full = k, v
            k_pos = positions[:Tsrc] if use_rope else jnp.arange(Tsrc)
            q_pos = positions

    if decode:
        out = _grouped_decode_attention(q, cache["k"], cache["v"],
                                        cache["index"], k, v, cache_pos,
                                        window, causal)
        out = out.reshape(B, S, n_heads * d_head) @ p["wo"]
        return out, new_cache

    if G > 1:
        # kv replication: repeat KV heads up to H so the flat head dim
        # shards on one mesh axis (caches store the original kv width).
        # k/v stay replicated over the model axis when kv doesn't divide
        # it; the q-head-sharded einsums then read them locally (no
        # involuntary resharding).
        k_full = jnp.repeat(k_full, G, axis=2)
        v_full = jnp.repeat(v_full, G, axis=2)
    else:
        # full MHA: k/v arrive head-sharded from the projections; pin that
        # sharding so it survives into the nested flash scan bodies
        # (without the pin, propagation degrades and XLA all-gathers K/V
        # chunks inside the loops - H-D2, EXPERIMENTS.md section Perf).
        k_full = sc(k_full, "heads")
        v_full = sc(v_full, "heads")
    q = sc(q, "heads")

    T = k_full.shape[1]
    if S % Q_CHUNK == 0 and T % KV_CHUNK == 0:
        out = flash_attention(q, k_full, v_full, q_pos, k_pos, window,
                              causal)
    else:
        out = _plain_attention(q, k_full, v_full, q_pos, k_pos, window,
                               causal)
    out = sc(out, "heads")
    out = out.reshape(B, S, n_heads * d_head) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# Dense (SwiGLU) MLP
# ---------------------------------------------------------------------------


def mlp_params(key, d_model: int, d_ff: int, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "wi_up": dense_init(ks[1], d_model, d_ff, dtype),
        "wo": dense_init(ks[2], d_ff, d_model, dtype),
    }


def mlp(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])) @ p["wo"]


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Numerically-stable CE; logits (B,S,V) any float dtype, targets int."""
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(x: jnp.ndarray, w: jnp.ndarray,
                         targets: jnp.ndarray,
                         mask: Optional[jnp.ndarray],
                         sc, chunk: int = 512) -> jnp.ndarray:
    """CE over the LM head without materializing full (B,S,V) logits.

    Scans sequence chunks, rematerializing each chunk's logits in the
    backward pass (jax.checkpoint).  Transient memory drops from
    O(B*S*V) to O(B*chunk*V) - required for 4k x 152k-vocab train cells.
    """
    B, S, D = x.shape
    if S <= chunk:
        logits = sc(x @ w, "logits")
        return cross_entropy(logits, targets, mask)
    n = S // chunk
    xc = x.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    mc = (mask.reshape(B, n, chunk).transpose(1, 0, 2) if mask is not None
          else jnp.ones((n, B, chunk), jnp.float32))

    @jax.checkpoint
    def body(carry, inp):
        xb, tb, mb = inp
        logits = sc(xb @ w, "logits").astype(jnp.float32)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mb
        return (carry[0] + nll.sum(), carry[1] + mb.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)
