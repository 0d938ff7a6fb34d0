"""Decoder-only transformers with GQA attention and a dense SwiGLU or a
top-k mixture-of-experts MLP: the weights the benchmark serves, the plain
reference it checks them against, and the work a step needs.

Everything is read from a configuration file's ``config`` (Hugging Face
keys, as the program runs them).  Nothing here imports the program.

* ``make_weights``: random weights from a key, in the parameter tree the
  program's ``prefill``/``decode_step`` read.  Rows of the embedding and
  columns of the LM head past the published vocabulary (the program pads it
  to a multiple of 128) are zero, as when published weights are padded.
* ``reference_logits``: the forward pass in float32 at HIGHEST matmul
  precision, full causal attention, dropless MoE, no cache and no batching
  tricks; layer by layer.  ``quant="fp8"`` rounds both operands of every
  weight matmul to float8_e4m3 (per-channel weight scales, per-token
  activation scales): the lower-precision control.
* ``prefill_work`` / ``decode_work``: FLOPs and HBM bytes a step needs,
  whatever implements it (see the functions).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# program ModelConfig attribute -> config key it must equal
PROGRAM_FIELDS = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "rms_norm_eps",
    "qk_norm": "qk_norm",
    "dtype": "torch_dtype",
    "n_experts": "num_local_experts",
    "n_experts_active": "num_experts_per_tok",
    "sliding_window": "sliding_window",
}
DENSE_FF_FIELD, MOE_FF_FIELD = "d_ff", "moe_d_ff"

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
ROUTER_BYTES = 4        # the program keeps the router in float32


def sizes(c: dict) -> dict:
    """The sizes every function here uses, with the family's defaults."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {
        "L": c["num_hidden_layers"], "D": d, "H": h,
        "KV": c["num_key_value_heads"],
        "Dh": c.get("head_dim") or d // h,
        "F": c["intermediate_size"], "V": c["vocab_size"],
        "Vp": -(-c["vocab_size"] // 128) * 128,
        "E": c.get("num_local_experts") or 0,
        "K": c.get("num_experts_per_tok") or 0,
        "qk_norm": bool(c.get("qk_norm", False)),
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "attn_mult": float(c.get("attention_multiplier")
                           or 1.0 / math.sqrt(c.get("head_dim") or d // h)),
        "emb_mult": float(c.get("embedding_multiplier", 1.0)),
        "res_mult": float(c.get("residual_multiplier", 1.0)),
        "logit_div": float(c.get("logits_scaling", 1.0)),
        "dtype": c.get("torch_dtype", "bfloat16"),
    }


def program_expect(c: dict) -> dict:
    """{ModelConfig attribute: value} the program's config must hold."""
    s = sizes(c)
    want = {attr: c.get(key) for attr, key in PROGRAM_FIELDS.items()}
    want["head_dim"] = s["Dh"]
    want["qk_norm"] = s["qk_norm"]
    want["sliding_window"] = c.get("sliding_window") or 0
    want["n_experts"], want["n_experts_active"] = s["E"], s["K"]
    want[MOE_FF_FIELD if s["E"] else DENSE_FF_FIELD] = s["F"]
    return want


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def make_weights(c: dict, key) -> dict:
    """Random weights in the program's parameter tree (jit this)."""
    s = sizes(c)
    L, D, H, KV, Dh, F, V, Vp, E = (s[k] for k in
                                    ("L", "D", "H", "KV", "Dh", "F", "V",
                                     "Vp", "E"))
    dt = jnp.dtype(s["dtype"])
    keys = iter(jax.random.split(key, 32))

    def dense(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dt)

    vocab_rows = (jnp.arange(Vp) < V)
    embed = jax.random.normal(next(keys), (Vp, D), jnp.float32) * 0.02
    lm_head = jax.random.normal(next(keys), (D, Vp), jnp.float32) / math.sqrt(D)
    attn = {"wq": dense((L, D, H * Dh), D), "wk": dense((L, D, KV * Dh), D),
            "wv": dense((L, D, KV * Dh), D), "wo": dense((L, H * Dh, D), H * Dh)}
    if s["qk_norm"]:
        attn["q_norm"] = norm((L, Dh))
        attn["k_norm"] = norm((L, Dh))
    layers = {"ln1": norm((L, D)), "attn": attn, "ln2": norm((L, D))}
    if E:
        layers["moe"] = {"router": dense((L, D, E), D, jnp.float32),
                         "wi_gate": dense((L, E, D, F), D),
                         "wi_up": dense((L, E, D, F), D),
                         "wo": dense((L, E, F, D), F)}
    else:
        layers["mlp"] = {"wi_gate": dense((L, D, F), D),
                         "wi_up": dense((L, D, F), D),
                         "wo": dense((L, F, D), F)}
    return {"embed": (embed * vocab_rows[:, None]).astype(dt),
            "final_norm": norm((D,)),
            "lm_head": (lm_head * vocab_rows[None, :]).astype(dt),
            "layers": layers}


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis):
    """Round x to float8_e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _matmul(x, w, quant):
    """x (..., I) @ w (I, O), both float32 values."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, T, heads, Dh); rotates the two halves of Dh (Qwen/Llama form)."""
    T, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s, quant, x, lw):
    f32 = lambda a: a.astype(jnp.float32)
    lw = jax.tree.map(f32, lw)
    B, T, _ = x.shape
    H, KV, Dh = s["H"], s["KV"], s["Dh"]
    a = lw["attn"]
    h = _rms(x, lw["ln1"], s["eps"])
    q = _matmul(h, a["wq"], quant).reshape(B, T, H, Dh)
    k = _matmul(h, a["wk"], quant).reshape(B, T, KV, Dh)
    v = _matmul(h, a["wv"], quant).reshape(B, T, KV, Dh)
    if s["qk_norm"]:
        q, k = _rms(q, a["q_norm"], s["eps"]), _rms(k, a["k_norm"], s["eps"])
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * s["attn_mult"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, T, H * Dh)
    x = x + s["res_mult"] * _matmul(att, a["wo"], quant)

    h = _rms(x, lw["ln2"], s["eps"])
    if "moe" in lw:
        m = lw["moe"]
        probs = jax.nn.softmax(h @ m["router"], axis=-1)        # (B,T,E)
        top, idx = jax.lax.top_k(probs, s["K"])
        gates = jnp.sum(jax.nn.one_hot(idx, s["E"]) * (top / top.sum(
            -1, keepdims=True))[..., None], axis=-2)            # (B,T,E)

        def expert(e_w):
            g, u, o = e_w
            return _matmul(jax.nn.silu(_matmul(h, g, quant))
                           * _matmul(h, u, quant), o, quant)

        outs = jax.lax.map(expert, (m["wi_gate"], m["wi_up"], m["wo"]))
        mlp = jnp.einsum("ebtd,bte->btd", outs, gates)
    else:
        m = lw["mlp"]
        mlp = _matmul(jax.nn.silu(_matmul(h, m["wi_gate"], quant))
                      * _matmul(h, m["wi_up"], quant), m["wo"], quant)
    return x + s["res_mult"] * mlp, None


def reference_logits(c: dict, w: dict, tokens, n_score: int,
                     quant: str = "none"):
    """Logits (B, n_score, V) in float32 at the last ``n_score`` positions
    of ``tokens`` (B, T), over the published vocabulary.  Call under
    ``jax.default_matmul_precision("highest")``."""
    s = sizes(c)
    x = w["embed"][tokens].astype(jnp.float32) * s["emb_mult"]
    x, _ = jax.lax.scan(lambda x, lw: _layer(s, quant, x, lw), x, w["layers"])
    x = _rms(x[:, -n_score:], w["final_norm"].astype(jnp.float32), s["eps"])
    head = w["lm_head"][:, :s["V"]].astype(jnp.float32)
    return _matmul(x, head, quant) / s["logit_div"]


# ---------------------------------------------------------------------------
# Work a step needs
# ---------------------------------------------------------------------------


def _per_token_matmul_params(s) -> tuple:
    """(attention projection weights per layer, MLP weights one token uses
    per layer: for MoE its routed experts and the router)."""
    D, H, KV, Dh, F, E, K = (s[k] for k in ("D", "H", "KV", "Dh", "F", "E",
                                            "K"))
    attn = D * H * Dh * 2 + D * KV * Dh * 2
    if E:
        return attn, K * 3 * D * F + D * E
    return attn, 3 * D * F


def _experts_touched(s, tokens: int) -> float:
    """Expected distinct experts a layer's ``tokens`` tokens route to, under
    uniform routing: E (1 - (1 - K/E)^tokens)."""
    E, K = s["E"], s["K"]
    return E * (1.0 - (1.0 - K / E) ** tokens)


def _weight_bytes(s, tokens: int) -> float:
    """Weights a step over ``tokens`` tokens must read once: attention,
    norms, the MLP (for MoE the router and the experts it routes to), the
    final norm and the LM head over the published vocabulary; of the
    embedding only the rows looked up."""
    b = DTYPE_BYTES[s["dtype"]]
    D, F, E, V, L, Dh = s["D"], s["F"], s["E"], s["V"], s["L"], s["Dh"]
    attn, _ = _per_token_matmul_params(s)
    norms = 2 * D + (2 * Dh if s["qk_norm"] else 0)
    if E:
        mlp = (_experts_touched(s, tokens) * 3 * D * F * b
               + D * E * ROUTER_BYTES)
    else:
        mlp = 3 * D * F * b
    per_layer = (attn + norms) * b + mlp
    return L * per_layer + (D + D * V) * b + min(tokens, V) * D * b


def _kv_bytes_per_position(s) -> int:
    return s["L"] * 2 * s["KV"] * s["Dh"] * DTYPE_BYTES[s["dtype"]]


def prefill_work(c: dict, batch: int, seq: int) -> tuple:
    """(FLOPs, bytes) of one prefill of ``batch`` prompts of ``seq`` tokens
    that returns last-position logits and fills the KV cache.  FLOPs: every
    token through the projections and its routed experts (MoE) or the MLP,
    causal attention counted as half of the full S x S, the LM head at the
    last position only.  Bytes: weights once, the KV of every position
    written once, the logits written."""
    s = sizes(c)
    attn, mlp_tok = _per_token_matmul_params(s)
    tokens = batch * seq
    flops = s["L"] * (2 * tokens * (attn + mlp_tok)
                      + batch * 2 * 2 * s["H"] * s["Dh"] * seq * (seq + 1) / 2)
    flops += batch * 2 * s["D"] * s["V"]
    b = DTYPE_BYTES[s["dtype"]]
    byts = (_weight_bytes(s, tokens) + tokens * _kv_bytes_per_position(s)
            + batch * s["V"] * b)
    return float(flops), float(byts)


def decode_work(c: dict, batch: int, held: int) -> tuple:
    """(FLOPs, bytes) of one decode step of ``batch`` lanes, each holding
    ``held`` earlier positions in its KV cache.  FLOPs: one token per lane
    through the projections, routed experts or MLP and the LM head, and
    attention over the held positions plus its own.  Bytes: weights once
    (for MoE the experts the batch can touch), the held K/V read once, the
    new position's K/V written, the logits written."""
    s = sizes(c)
    attn, mlp_tok = _per_token_matmul_params(s)
    flops = batch * (s["L"] * (2 * (attn + mlp_tok)
                               + 2 * 2 * s["H"] * s["Dh"] * (held + 1))
                     + 2 * s["D"] * s["V"])
    b = DTYPE_BYTES[s["dtype"]]
    byts = (_weight_bytes(s, batch) + batch * (held + 1)
            * _kv_bytes_per_position(s) + batch * s["V"] * b)
    return float(flops), float(byts)
