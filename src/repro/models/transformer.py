"""Model assembly: embeddings -> scanned block stack -> LM head.

One composable decoder covers every architecture of ``repro.configs``; the
layer kind comes from ``cfg.block_pattern`` ("attn" | "moe" | "mamba2" |
"mamba2_moe" | "rwkv6"), cycled over the layers.  Each run of same-kind
layers is one layer scan over its own stacked weights and caches: a model
of one kind has one run, a hybrid (granite-4.0-h: Mamba2 and attention
layers, each followed by an MoE) one per run, in published order.  Three
structural extensions:

* zamba2: a *shared* attention+MLP block (single parameter set) applied every
  ``cfg.shared_attn_every`` SSM layers - handled inside the layer scan with
  ``lax.cond`` so the stack still compiles as one scan;
* whisper: an encoder stack plus cross-attention in every decoder block;
* VLM/audio frontends: stubs per the assignment - ``batch["patches"]`` /
  ``batch["frames"]`` are precomputed embeddings, linearly projected and
  prepended (VLM) or fed to the encoder (audio).

Three execution modes share the block code:
  train   : full sequence, no caches, remat + scan;
  prefill : full sequence, caches written (ring buffers / SSM states);
  decode  : single token against the caches (the ``serve_step``).

Sharding: the model code is mesh-agnostic; an optional ``sc`` callback
(``repro.parallel.sharding.ShardingRules.constrain``) pins the residual
stream / logits / caches to the mesh at block boundaries.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from . import layers as L
from . import mamba2 as M
from . import moe as MOE
from . import rwkv6 as R

Params = Dict[str, Any]
_id_sc = lambda x, kind=None: x
# ring lengths are a multiple of a 16-bit value's sublane tile on a TPU
RING_ALIGN = 16


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def _attn_block_params(cfg: ModelConfig, key, cross: bool, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "ln1": L.rms_norm_init(cfg.d_model, dtype),
        "attn": L.attention_params(ks[0], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim,
                                   cfg.qk_norm, dtype),
        "ln2": L.rms_norm_init(cfg.d_model, dtype),
    }
    if cross:
        p["ln_cross"] = L.rms_norm_init(cfg.d_model, dtype)
        p["cross"] = L.attention_params(ks[1], cfg.d_model, cfg.n_heads,
                                        cfg.n_kv_heads, cfg.head_dim,
                                        False, dtype)
    return p


def _moe_params(cfg: ModelConfig, key, dtype) -> Params:
    return MOE.moe_params(key, cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                          dtype, held=cfg.experts_held,
                          shared_d_ff=cfg.shared_d_ff)


def _by_run(stacks: tuple):
    """A model's "layers": the one run's stack, or a tuple of the runs'."""
    return stacks[0] if len(stacks) == 1 else stacks


def _layer_params(cfg: ModelConfig, kind: str, key, dtype,
                  decoder: bool = True) -> Params:
    ks = jax.random.split(key, 2)
    if kind == "attn":
        p = _attn_block_params(cfg, ks[0], cfg.is_encdec and decoder, dtype)
        p["mlp"] = L.mlp_params(ks[1], cfg.d_model, cfg.d_ff, dtype)
        return p
    if kind == "moe":
        p = _attn_block_params(cfg, ks[0], False, dtype)
        p["moe"] = _moe_params(cfg, ks[1], dtype)
        return p
    if kind in ("mamba2", "mamba2_moe"):
        p = {
            "ln1": L.rms_norm_init(cfg.d_model, dtype),
            "mamba": M.mamba2_params(ks[0], cfg.d_model, cfg.d_inner,
                                     cfg.ssm_state, cfg.ssm_heads,
                                     cfg.ssm_conv, dtype),
        }
        if kind == "mamba2_moe":
            p["ln2"] = L.rms_norm_init(cfg.d_model, dtype)
            p["moe"] = _moe_params(cfg, ks[1], dtype)
        return p
    if kind == "rwkv6":
        return {
            "ln1": L.rms_norm_init(cfg.d_model, dtype),
            "ln2": L.rms_norm_init(cfg.d_model, dtype),
            "rwkv": R.rwkv6_params(ks[0], cfg.d_model, cfg.d_ff,
                                   cfg.rwkv_heads, cfg.rwkv_head_dim, dtype),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)

    params: Params = {
        "embed": L.embed_init(keys[0], cfg.vocab_padded, cfg.d_model, dtype),
        "final_norm": L.rms_norm_init(cfg.d_model, dtype),
        "lm_head": L.dense_init(keys[1], cfg.d_model, cfg.vocab_padded,
                                dtype),
    }

    layer_keys = jax.random.split(keys[2], cfg.n_layers)
    params["layers"] = _by_run(tuple(
        jax.vmap(lambda k, kind=kind: _layer_params(cfg, kind, k, dtype))(
            layer_keys[first:first + n])
        for kind, first, n in cfg.layer_runs()))

    if cfg.shared_attn_every:
        p = _attn_block_params(cfg, keys[3], False, dtype)
        p["mlp"] = L.mlp_params(keys[4], cfg.d_model, cfg.d_ff, dtype)
        params["shared_attn"] = p

    if cfg.is_encdec:
        enc_keys = jax.random.split(keys[5], cfg.n_enc_layers)

        def enc_layer(k):
            kk = jax.random.split(k, 2)
            p = _attn_block_params(cfg, kk[0], False, dtype)
            p["mlp"] = L.mlp_params(kk[1], cfg.d_model, cfg.d_ff, dtype)
            return p

        params["enc_layers"] = jax.vmap(enc_layer)(enc_keys)
        params["enc_norm"] = L.rms_norm_init(cfg.d_model, dtype)

    if cfg.frontend != "none":
        params["frontend_proj"] = L.dense_init(
            keys[6], cfg.frontend_dim, cfg.d_model, dtype)
    return params


def param_shapes(cfg: ModelConfig):
    """ShapeDtypeStruct pytree without allocating (for the dry-run)."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0))


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _attn_cache(cfg: ModelConfig, B: int, max_len: int, dtype) -> Dict:
    """K/V ring buffers, lane-dense (B, Tc, kv*dh): see
    ``layers.multihead_attention``.  Tc holds max_len tokens, or the window,
    rounded up to whole sublane tiles (``RING_ALIGN``): slots never written
    are masked, and the TPU then lays the ring out row-major with no
    padding, as the decode kernel reads it."""
    Tc = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    Tc = -(-Tc // RING_ALIGN) * RING_ALIGN
    shape = (B, Tc, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_cache(cfg: ModelConfig, B: int, max_len: int,
               enc_len: int = 0) -> Dict:
    """Zeros cache pytree (use jax.eval_shape on this for the dry-run).

    "layers" holds each run's stacked per-layer caches (see ``_by_run``):
    K/V rings for attention layers, SSM and conv states for Mamba2 layers,
    WKV state and shifts for RWKV6 layers; a hybrid holds both kinds side by
    side, one stack per run."""
    dtype = jnp.dtype(cfg.dtype)
    Ld = cfg.n_layers

    def stack(n, tree_fn):
        one = tree_fn()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), one)

    def layer_cache(kind):
        if kind in ("attn", "moe"):
            return _attn_cache(cfg, B, max_len, dtype)
        if kind in ("mamba2", "mamba2_moe"):
            kconv = cfg.ssm_conv - 1
            return {
                "ssm": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim,
                                  cfg.ssm_state), jnp.float32),
                "conv": {
                    "x": jnp.zeros((B, kconv, cfg.d_inner), dtype),
                    "B": jnp.zeros((B, kconv, cfg.ssm_state), dtype),
                    "C": jnp.zeros((B, kconv, cfg.ssm_state), dtype),
                },
            }
        P = cfg.rwkv_head_dim
        return {
            "wkv": jnp.zeros((B, cfg.rwkv_heads, P, P), jnp.float32),
            "tm_shift": jnp.zeros((B, 1, cfg.d_model), dtype),
            "cm_shift": jnp.zeros((B, 1, cfg.d_model), dtype),
        }

    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    cache["layers"] = _by_run(tuple(
        stack(n, lambda kind=kind: layer_cache(kind))
        for kind, _, n in cfg.layer_runs()))
    if cfg.shared_attn_every:
        n_inv = cfg.n_layers // cfg.shared_attn_every
        one = _attn_cache(cfg, B, max_len, dtype)
        cache["shared"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_inv,) + a.shape), one)
    if cfg.is_encdec:
        shape = (Ld, B, enc_len, cfg.n_kv_heads * cfg.head_dim)
        cache["cross"] = {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
    return cache


def cache_shapes(cfg: ModelConfig, B: int, max_len: int, enc_len: int = 0):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, B, max_len, enc_len))


# ---------------------------------------------------------------------------
# Blocks (shared across modes)
# ---------------------------------------------------------------------------


def _scaled(x, m: Optional[float]):
    """x times a configuration scalar, in float32, back in x's dtype; x
    itself where the scalar is absent (None), so no multiply is traced."""
    if m is None:
        return x
    return (x.astype(jnp.float32) * m).astype(x.dtype)


def _apply_attn_block(cfg, p, x, positions, cache, cache_pos, *, decode,
                      causal, cross_src, cross_cache, sc):
    """attn (+cross) (+mlp/moe) block. Returns (x, new_cache, new_cross, aux)."""
    aux: Dict[str, jnp.ndarray] = {}
    res = cfg.residual_multiplier
    q_scale = (None if cfg.attention_multiplier is None else
               cfg.attention_multiplier * math.sqrt(cfg.head_dim))
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    attn_out, new_cache = L.multihead_attention(
        p["attn"], h, positions, None, cache, cache_pos,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
        qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta if cfg.rope else None,
        window=cfg.sliding_window, causal=causal, decode=decode,
        eps=cfg.norm_eps, q_scale=q_scale, sc=sc)
    x = sc(x + _scaled(attn_out, res), "residual")

    new_cross = cross_cache
    if "cross" in p:
        hc = L.rms_norm(x, p["ln_cross"], cfg.norm_eps)
        cross_out, new_cross = L.multihead_attention(
            p["cross"], hc, positions, cross_src, cross_cache, cache_pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, d_head=cfg.head_dim,
            causal=False, decode=decode, is_cross=True, eps=cfg.norm_eps,
            sc=sc)
        x = sc(x + _scaled(cross_out, res), "residual")

    x, aux = _apply_mlp(cfg, p, x, sc)
    return x, new_cache, new_cross, aux


def _apply_mlp(cfg, p, x, sc):
    """The block's MLP after its norm: dense, or the MoE (with its shared
    expert); then the residual add.  Returns (x, aux)."""
    aux: Dict[str, jnp.ndarray] = {}
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        out, aux = MOE.moe_mlp(p["moe"], h2, n_experts=cfg.n_experts,
                               top_k=cfg.n_experts_active)
    else:
        out = L.mlp(p["mlp"], h2)
    return sc(x + _scaled(out, cfg.residual_multiplier), "residual"), aux


def _apply_mamba_block(cfg, p, x, cache, *, decode, sc):
    """Mamba2 mixer (+ the MoE of "mamba2_moe").  Returns (x, new_cache,
    aux)."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    kw = dict(d_inner=cfg.d_inner, n_state=cfg.ssm_state,
              n_heads=cfg.ssm_heads, head_dim=cfg.ssm_head_dim,
              eps=cfg.norm_eps)
    if decode:
        out, ssm, conv = M.mamba2_decode_step(
            p["mamba"], h, cache["ssm"], cache["conv"], **kw)
        new_cache = {"ssm": ssm, "conv": conv}
    elif cache is not None:  # prefill: thread states through
        out, (ssm, conv) = M.mamba2_forward(
            p["mamba"], h, ssm_state=cache["ssm"], conv_state=cache["conv"],
            return_state=True, **kw)
        new_cache = {"ssm": ssm.astype(cache["ssm"].dtype), "conv": conv}
    else:
        out = M.mamba2_forward(p["mamba"], h, **kw)
        new_cache = None
    x = sc(x + _scaled(out, cfg.residual_multiplier), "residual")
    aux: Dict[str, jnp.ndarray] = {}
    if "moe" in p:
        x, aux = _apply_mlp(cfg, p, x, sc)
    return x, new_cache, aux


def _apply_rwkv_block(cfg, p, x, cache, *, decode, sc):
    kw = dict(n_heads=cfg.rwkv_heads, head_dim=cfg.rwkv_head_dim)
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if decode:
        tm_out, tm_shift, wkv = R.rwkv6_time_mix_step(
            p["rwkv"], h, cache["tm_shift"], cache["wkv"], **kw)
        x = sc(x + tm_out, "residual")
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        cm_out, cm_shift = R.rwkv6_channel_mix_step(
            p["rwkv"], h2, cache["cm_shift"])
        x = sc(x + cm_out, "residual")
        return x, {"wkv": wkv, "tm_shift": tm_shift, "cm_shift": cm_shift}
    if cache is not None:  # prefill
        tm_out, tm_shift, wkv = R.rwkv6_time_mix(
            p["rwkv"], h, shift_state=cache["tm_shift"],
            wkv_state=cache["wkv"], return_state=True, **kw)
        x = sc(x + tm_out, "residual")
        h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        cm_out, cm_shift = R.rwkv6_channel_mix(
            p["rwkv"], h2, shift_state=cache["cm_shift"], return_state=True)
        x = sc(x + cm_out, "residual")
        return x, {"wkv": wkv.astype(cache["wkv"].dtype),
                   "tm_shift": tm_shift, "cm_shift": cm_shift}
    x = sc(x + R.rwkv6_time_mix(p["rwkv"], h, **kw), "residual")
    h2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    x = sc(x + R.rwkv6_channel_mix(p["rwkv"], h2), "residual")
    return x, None


# ---------------------------------------------------------------------------
# Stack (scan over layers)
# ---------------------------------------------------------------------------


def _stack(cfg: ModelConfig, params: Params, x, positions, caches,
           cache_pos, *, decode: bool, cross_src, sc, remat: bool):
    """Run the decoder stack: one layer scan per run of same-kind layers
    (``cfg.layer_runs()``).  caches: the stacked per-layer caches or None.

    In decode the K/V rings are read-only in the scan and not among its
    xs: each layer reads its ring from the stack by index (see
    ``layers.multihead_attention``) and hands back its new tokens' k/v,
    written into the stacked ring after the scan, so a step over a donated
    cache writes those tokens and copies no cache, nor any layer's ring.
    Mamba2 states ride in the scan's carry: each layer reads its states
    from the stack by index and writes its new ones over them, so a step
    rewrites each state once, in place.  rwkv6 states are rewritten whole
    every step."""
    runs = cfg.layer_runs()
    one = len(runs) == 1
    layer_params = (params["layers"],) if one else params["layers"]
    run_caches = (None if caches is None else
                  (caches["layers"],) if one else caches["layers"])
    cross_caches = caches.get("cross") if (caches is not None
                                           and cfg.is_encdec) else None
    shared = caches.get("shared") if (caches is not None
                                      and cfg.shared_attn_every) else None

    new_runs, auxes = [], []
    for r, (kind, first, n) in enumerate(runs):
        x, shared, new_lcache, aux = _run(
            cfg, params, kind, first, n, layer_params[r],
            None if caches is None else run_caches[r], x, shared, positions,
            cache_pos, decode=decode, cross_caches=cross_caches,
            cross_src=cross_src, sc=sc, remat=remat)
        new_runs.append(new_lcache)
        auxes.append(aux)
    aux = auxes[0] if one else {
        k: jnp.concatenate([a[k] for a in auxes if k in a])
        for k in dict.fromkeys(k for a in auxes for k in a)}

    new_caches = None
    if caches is not None:
        layers_out = [c for c, _ in new_runs]
        new_caches = dict(caches)
        new_caches["layers"] = _by_run(tuple(layers_out))
        if cfg.is_encdec and not decode:
            new_caches["cross"] = new_runs[0][1]
        if cfg.shared_attn_every:
            new_caches["shared"] = shared
    # aux scanned outputs: MoE rows summed and the largest group maxed over
    # layers, the rest averaged
    reduce = {"moe_rows": jnp.sum, "moe_max_group": jnp.max,
              "moe_rows_elsewhere": jnp.sum}
    aux = {k: reduce.get(k, jnp.mean)(v) for k, v in aux.items()}
    return x, new_caches, aux


def _run(cfg: ModelConfig, params: Params, kind: str, first: int, n: int,
         run_params, layer_caches, x, shared0, positions, cache_pos, *,
         decode: bool, cross_caches, cross_src, sc, remat: bool):
    """One run of ``n`` layers of ``kind`` from layer ``first`` on, as one
    layer scan.  Returns (x, zamba2's shared cache, (the run's new caches,
    whisper's new cross caches), the scanned aux)."""
    rings = decode and kind in ("attn", "moe")
    states = decode and kind in ("mamba2", "mamba2_moe")

    def unit(carry, xs):
        x, shared_cache, *carried = carry
        lp, lcache, idx, lcross = xs
        # the layer's index in its run's stacks (idx itself in a first run)
        local = idx - first if first else idx
        if rings:
            lcache = dict(layer_caches, index=local)
        if states:
            lcache = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, local, 0, False),
                carried[0])
        if decode and cross_caches is not None:
            lcross = dict(cross_caches, index=idx)
        aux = {}
        if kind in ("attn", "moe"):
            x, new_lcache, new_lcross, aux = _apply_attn_block(
                cfg, lp, x, positions, lcache, cache_pos,
                decode=decode, causal=True, cross_src=cross_src,
                cross_cache=lcross, sc=sc)
        elif kind in ("mamba2", "mamba2_moe"):
            x, new_lcache, aux = _apply_mamba_block(cfg, lp, x, lcache,
                                                    decode=decode, sc=sc)
            new_lcross = lcross
        else:
            x, new_lcache = _apply_rwkv_block(cfg, lp, x, lcache,
                                              decode=decode, sc=sc)
            new_lcross = lcross
        if states:      # the new states over the old, in the carry
            carried = [jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(
                    a, u.astype(a.dtype), local, 0),
                carried[0], new_lcache)]
            new_lcache = None

        # zamba2 shared attention block every k layers
        if cfg.shared_attn_every:
            k = cfg.shared_attn_every
            inv = idx // k

            def with_shared(operands):
                x, shared_cache = operands
                sp = params["shared_attn"]
                if shared_cache is None:
                    scache = None
                elif decode:
                    scache = dict(shared_cache, index=inv)
                else:
                    scache = jax.tree.map(lambda a: a[inv], shared_cache)
                x2, new_scache, _, _ = _apply_attn_block(
                    cfg, sp, x, positions, scache, cache_pos,
                    decode=decode, causal=True, cross_src=None,
                    cross_cache=None, sc=sc)
                if decode:      # the block returned the new tokens' k/v
                    shared_cache = jax.tree.map(
                        lambda buf, new: L.ring_update(
                            buf, new[None], cache_pos, axis=2, first=inv),
                        shared_cache, new_scache)
                elif shared_cache is not None:
                    shared_cache = jax.tree.map(
                        lambda buf, upd: buf.at[inv].set(upd),
                        shared_cache, new_scache)
                return x2, shared_cache

            def without_shared(operands):
                return operands

            x, shared_cache = jax.lax.cond(
                (idx + 1) % k == 0, with_shared, without_shared,
                (x, shared_cache))

        return (x, shared_cache, *carried), (new_lcache, new_lcross, aux)

    unit_fn = jax.checkpoint(unit) if remat else unit

    idxs = jnp.arange(first, first + n)
    xs = (run_params, None if rings or states else layer_caches, idxs,
          None if decode else cross_caches)
    carry = (x, shared0) + ((layer_caches,) if states else ())
    (x, shared_out, *carried), (new_layer_caches, new_cross, aux) = \
        jax.lax.scan(unit_fn, carry, xs)

    if states:
        new_layer_caches = carried[0]
    elif rings:
        # ys holds the new tokens (L, B, S, kv*dh): the ring's T is axis 2
        new_layer_caches = jax.tree.map(
            lambda buf, new: L.ring_update(buf, new, cache_pos, axis=2),
            layer_caches, new_layer_caches)
    return x, shared_out, (new_layer_caches, new_cross), aux


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def _encode(cfg: ModelConfig, params: Params, frames, sc, remat: bool):
    """frames: (B, T_enc, frontend_dim) precomputed embeddings (stub)."""
    x = frames @ params["frontend_proj"]
    x = sc(x, "residual")
    positions = jnp.arange(x.shape[1])

    def unit(x, lp):
        x, _, _, _ = _apply_attn_block(
            cfg, lp, x, positions, None, None, decode=False, causal=False,
            cross_src=None, cross_cache=None, sc=sc)
        return x, None

    unit_fn = jax.checkpoint(unit) if remat else unit
    x, _ = jax.lax.scan(unit_fn, x, params["enc_layers"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict,
                  sc) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Returns (decoder input embeddings, loss mask or None)."""
    tok = batch["tokens"]
    x = _scaled(params["embed"][tok], cfg.embedding_multiplier)
    mask = None
    if cfg.frontend == "vision_stub":
        patches = batch["patches"] @ params["frontend_proj"]  # (B,P,D)
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        B, P = patches.shape[0], patches.shape[1]
        mask = jnp.concatenate(
            [jnp.zeros((B, P), jnp.float32),
             jnp.ones((B, tok.shape[1]), jnp.float32)], axis=1)
    return sc(x, "residual"), mask


def forward_hidden(cfg: ModelConfig, params: Params, batch: Dict,
                   sc: Callable = _id_sc, remat: bool = True):
    """Full-sequence pass without caches, the body of ``forward_train``.

    Returns (final-normed hidden states (B, S, D), loss mask or None, aux);
    ``lm_logits(cfg, params, hidden)`` gives the logits at every position."""
    x, mask = _embed_inputs(cfg, params, batch, sc)
    positions = jnp.arange(x.shape[1])

    cross_src = None
    if cfg.is_encdec:
        cross_src = _encode(cfg, params, batch["frames"], sc, remat)

    x, _, aux = _stack(cfg, params, x, positions, None, None,
                       decode=False, cross_src=cross_src, sc=sc, remat=remat)
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), mask, aux


def _logit_scale(cfg: ModelConfig) -> Optional[float]:
    return None if cfg.logits_scaling is None else 1.0 / cfg.logits_scaling


def lm_logits(cfg: ModelConfig, params: Params, hidden, sc: Callable = _id_sc):
    """Logits of final-normed hidden states (divided by ``logits_scaling``
    where the configuration has it)."""
    return _scaled(sc(hidden @ params["lm_head"], "logits"),
                   _logit_scale(cfg))


def forward_train(cfg: ModelConfig, params: Params, batch: Dict,
                  sc: Callable = _id_sc, remat: bool = True):
    """Full-sequence forward; returns (loss, metrics)."""
    x, mask, aux = forward_hidden(cfg, params, batch, sc, remat)

    targets = batch["targets"]
    if cfg.frontend == "vision_stub":
        # patch positions carry no targets: prepend ignore labels
        B, P = x.shape[0], x.shape[1] - targets.shape[1]
        targets = jnp.concatenate(
            [jnp.zeros((B, P), targets.dtype), targets], axis=1)
    # logits / s == (hidden / s) @ lm_head: the chunked loss never holds them
    x = _scaled(x, _logit_scale(cfg))
    loss = L.chunked_softmax_xent(x, params["lm_head"], targets, mask, sc)
    for k, v in aux.items():
        if k.endswith("_loss"):
            loss = loss + 0.01 * v
    metrics = {"loss": loss, **aux}
    return loss, metrics


def prefill(cfg: ModelConfig, params: Params, batch: Dict, max_len: int,
            sc: Callable = _id_sc, return_aux: bool = False):
    """Process the prompt; returns (last-token logits, populated cache), and
    with ``return_aux`` the stack's aux metrics third."""
    x, _ = _embed_inputs(cfg, params, batch, sc)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)

    enc_len = 0
    cross_src = None
    if cfg.is_encdec:
        cross_src = _encode(cfg, params, batch["frames"], sc, remat=False)
        enc_len = cross_src.shape[1]

    caches = init_cache(cfg, B, max_len, enc_len)
    x, caches, aux = _stack(cfg, params, x, positions, caches, 0,
                            decode=False, cross_src=cross_src, sc=sc,
                            remat=False)
    caches["pos"] = jnp.asarray(S, jnp.int32)

    x = L.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x, sc)
    return (logits, caches, aux) if return_aux else (logits, caches)


def decode_step(cfg: ModelConfig, params: Params, caches: Dict,
                tokens: jnp.ndarray, sc: Callable = _id_sc,
                return_aux: bool = False):
    """One serving step: tokens (B, 1) -> (logits (B,1,V), updated caches),
    and with ``return_aux`` the stack's aux metrics third."""
    x = sc(_scaled(params["embed"][tokens], cfg.embedding_multiplier),
           "residual")
    pos = caches["pos"]
    positions = pos + jnp.arange(tokens.shape[1])

    x, new_caches, aux = _stack(cfg, params, x, positions, caches, pos,
                                decode=True, cross_src=None, sc=sc,
                                remat=False)
    new_caches["pos"] = pos + tokens.shape[1]

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x, sc)
    return (logits, new_caches, aux) if return_aux else (logits, new_caches)
