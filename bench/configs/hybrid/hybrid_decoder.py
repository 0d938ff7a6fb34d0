"""Hybrid decoders of Mamba2 and NoPE attention layers, each followed by a
top-k mixture-of-experts MLP and a shared SwiGLU expert (Granite 4.0-H,
``granitemoehybrid``): the weights the benchmark serves, the plain
reference it checks them against, and the work a step needs.

Everything is read from a configuration file's ``config`` (Hugging Face
keys, as the program runs them).  Nothing here imports the program;
``decoder.py`` (its norm, float8 rounding and dtype sizes) is loaded from
the directory above.

Layer ``i`` is ``layer_types[i]``, "mamba" or "attention", and computes

    h   = x + res_mult * mixer(rms(x))
    out = h + res_mult * (moe(rms(h)) + shared_mlp(rms(h)))

* Mamba2 mixer (n_groups 1): z, x, B, C, dt projections of the normed
  input; a depthwise causal conv of width ``mamba_d_conv`` with bias, then
  SiLU, over x, B and C; dt = softplus(dt + dt_bias), A = -exp(A_log); per
  token and head the recurrence h <- exp(dt A) h + dt x (outer) B,
  y = C . h + D x; then RMSNorm(y * silu(z)) over the inner width and the
  output projection.
* Attention: GQA with no position embedding (``position_embedding_type``
  "nope"), full causal, scores scaled by ``attention_multiplier``.
* MoE: the router's softmax over all ``num_router_experts``, the top
  ``num_experts_per_tok``, renormalised (a softmax over the chosen ones);
  only the ``num_local_experts`` held here (experts 0 ..) are computed,
  with their gates as routed.  The shared expert is computed in full.

``make_weights``: random weights from a key in the program's parameter
tree: per run of same-kind layers one stack, as ``repro``'s ``init_cache``
lays out its caches.  ``reference_logits``: the forward pass in float32 at
HIGHEST precision, the SSM as a plain per-token recurrence (no chunked
form), no cache and no batching tricks; ``quant="fp8"`` is the control,
rounding to float8_e4m3 (one scale per token for activations, per output
channel for weights) wherever the bfloat16 program holds a value in
bfloat16, and keeping the SSM state in float32 as the program does.
``prefill_work`` / ``decode_work``: FLOPs and HBM bytes a step needs.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_hybrid_decoder_base",
    Path(__file__).resolve().parent.parent / "decoder.py")
decoder = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(decoder)

DTYPE_BYTES = decoder.DTYPE_BYTES
ROUTER_BYTES = decoder.ROUTER_BYTES
STATE_BYTES = 4         # the program keeps the SSM state in float32
KINDS = {"mamba": "mamba2_moe", "attention": "moe"}   # the program's kinds


def sizes(c: dict) -> dict:
    """The sizes every function here uses."""
    if c.get("mamba_n_groups", 1) != 1:
        raise ValueError("only mamba_n_groups 1 is described here")
    d, h = c["hidden_size"], c["num_attention_heads"]
    L = c["num_hidden_layers"]
    di = c["mamba_expand"] * d
    s = {
        "L": L, "D": d, "H": h, "KV": c["num_key_value_heads"],
        "Dh": c.get("head_dim") or d // h,
        "F": c["intermediate_size"], "Fs": c["shared_intermediate_size"],
        "V": c["vocab_size"], "Vp": -(-c["vocab_size"] // 128) * 128,
        "E": c["num_router_experts"], "held": c["num_local_experts"],
        "K": c["num_experts_per_tok"],
        "di": di, "N": c["mamba_d_state"], "Hm": c["mamba_n_heads"],
        "P": c["mamba_d_head"], "conv": c["mamba_d_conv"],
        "eps": float(c["rms_norm_eps"]),
        "attn_mult": float(c["attention_multiplier"]),
        "emb_mult": float(c["embedding_multiplier"]),
        "res_mult": float(c["residual_multiplier"]),
        "logit_div": float(c["logits_scaling"]),
        "dtype": c.get("torch_dtype", "bfloat16"),
        "kinds": tuple(c["layer_types"][:L]),
    }
    if s["Hm"] * s["P"] != di:
        raise ValueError("mamba_n_heads x mamba_d_head must be the inner "
                         "width")
    return s


def runs(s) -> list:
    """(kind, first layer, layers) of each run of same-kind layers."""
    out = []
    for i, k in enumerate(s["kinds"]):
        if out and out[-1][0] == k:
            out[-1][2] += 1
        else:
            out.append([k, i, 1])
    return [tuple(r) for r in out]


def program_expect(c: dict) -> dict:
    """{ModelConfig attribute: value} the program's config must hold."""
    s = sizes(c)
    return {
        "n_layers": s["L"], "d_model": s["D"], "n_heads": s["H"],
        "n_kv_heads": s["KV"], "head_dim": s["Dh"],
        "vocab_size": s["V"], "norm_eps": s["eps"], "dtype": s["dtype"],
        "block_pattern": tuple(KINDS[k] for k in s["kinds"]),
        "rope": c["position_embedding_type"] != "nope",
        "qk_norm": False, "sliding_window": 0,
        "n_experts": s["E"], "experts_held": s["held"],
        "n_experts_active": s["K"], "moe_d_ff": s["F"],
        "shared_d_ff": s["Fs"],
        "ssm_state": s["N"], "ssm_conv": s["conv"],
        "ssm_heads": s["Hm"], "ssm_head_dim": s["P"], "d_inner": s["di"],
        "embedding_multiplier": s["emb_mult"],
        "attention_multiplier": s["attn_mult"],
        "residual_multiplier": s["res_mult"],
        "logits_scaling": s["logit_div"],
    }


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def make_weights(c: dict, key) -> dict:
    """Random weights in the program's parameter tree (jit this)."""
    s = sizes(c)
    D, F, Fs, V, Vp = s["D"], s["F"], s["Fs"], s["V"], s["Vp"]
    di, N, Hm, Kc = s["di"], s["N"], s["Hm"], s["conv"]
    dt = jnp.dtype(s["dtype"])
    keys = iter(jax.random.split(key, 64))

    def normal(shape, scale, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def dense(shape, fan_in, dtype=dt):
        return normal(shape, 1.0 / math.sqrt(fan_in), dtype)

    def norm(shape):
        return (1.0 + normal(shape, 0.1, jnp.float32)).astype(dt)

    def moe(n):
        return {"router": dense((n, D, s["E"]), D, jnp.float32),
                "wi_gate": dense((n, s["held"], D, F), D),
                "wi_up": dense((n, s["held"], D, F), D),
                "wo": dense((n, s["held"], F, D), F),
                "shared_mlp": {"wi_gate": dense((n, D, Fs), D),
                               "wi_up": dense((n, D, Fs), D),
                               "wo": dense((n, Fs, D), Fs)}}

    def mamba(n):
        # Mamba2's initial ranges: A in [1, 16], dt in [0.001, 0.1]
        u = jax.random.uniform(next(keys), (2, n, Hm), jnp.float32)
        step = jnp.exp(u[1] * (math.log(0.1) - math.log(0.001))
                       + math.log(0.001))
        return {"w_z": dense((n, D, di), D), "w_x": dense((n, D, di), D),
                "w_B": dense((n, D, N), D), "w_C": dense((n, D, N), D),
                "w_dt": dense((n, D, Hm), D),
                "conv_x_w": dense((n, Kc, di), Kc),
                "conv_x_b": normal((n, di), 0.1),
                "conv_B_w": dense((n, Kc, N), Kc),
                "conv_B_b": normal((n, N), 0.1),
                "conv_C_w": dense((n, Kc, N), Kc),
                "conv_C_b": normal((n, N), 0.1),
                "A_log": jnp.log(1.0 + 15.0 * u[0]),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
                "D": 1.0 + normal((n, Hm), 0.1, jnp.float32),
                "norm_w": norm((n, di)),
                "out_proj": dense((n, di, D), di)}

    def attention(n):
        H, KV, Dh = s["H"], s["KV"], s["Dh"]
        return {"wq": dense((n, D, H * Dh), D),
                "wk": dense((n, D, KV * Dh), D),
                "wv": dense((n, D, KV * Dh), D),
                "wo": dense((n, H * Dh, D), H * Dh)}

    layers = []
    for kind, _, n in runs(s):
        lw = {"ln1": norm((n, D)), "ln2": norm((n, D)), "moe": moe(n)}
        if kind == "mamba":
            lw["mamba"] = mamba(n)
        else:
            lw["attn"] = attention(n)
        layers.append(lw)
    vocab_rows = jnp.arange(Vp) < V
    embed = normal((Vp, D), 0.02, jnp.float32)
    lm_head = normal((D, Vp), 1.0 / math.sqrt(D), jnp.float32)
    return {"embed": (embed * vocab_rows[:, None]).astype(dt),
            "final_norm": norm((D,)),
            "lm_head": (lm_head * vocab_rows[None, :]).astype(dt),
            "layers": layers[0] if len(layers) == 1 else tuple(layers)}


# ---------------------------------------------------------------------------
# Plain reference
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    return decoder._rms(x, w, eps)


def _ops(quant: str):
    """(hold, matmul): ``hold`` rounds a value the bfloat16 program holds
    in bfloat16 (the identity in float32); ``matmul`` x (..., I) @ w (I, O)."""
    if quant == "none":
        return (lambda x: x), (lambda x, w: x @ w)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    return (lambda x: decoder._fp8(x, -1)), (
        lambda x, w: decoder._matmul(x, w, "fp8"))


def _conv(x, w, b):
    """Depthwise causal conv as a plain sum: out[t] = b + sum_i w[i]
    x[t - (K-1) + i], zeros before the first token.  x (B, T, C)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[i] * xp[:, i:i + T] for i in range(K))


def _mamba(s, hold, mm, h, m):
    B, T, _ = h.shape
    Hm, P = s["Hm"], s["P"]
    z = hold(mm(h, m["w_z"]))
    xs = hold(mm(h, m["w_x"]))
    Bm = hold(mm(h, m["w_B"]))
    Cm = hold(mm(h, m["w_C"]))
    dt = hold(mm(h, m["w_dt"]))
    xs = hold(jax.nn.silu(_conv(xs, m["conv_x_w"], m["conv_x_b"])))
    Bm = hold(jax.nn.silu(_conv(Bm, m["conv_B_w"], m["conv_B_b"])))
    Cm = hold(jax.nn.silu(_conv(Cm, m["conv_C_w"], m["conv_C_b"])))
    dt = jax.nn.softplus(dt + m["dt_bias"])                     # (B, T, Hm)
    A = -jnp.exp(m["A_log"])
    xh = xs.reshape(B, T, Hm, P)

    def step(state, inp):                                    # (B, Hm, P, N)
        x_t, B_t, C_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", state, C_t) + m["D"][:, None] * x_t
        return state, y

    state0 = jnp.zeros((B, Hm, P, s["N"]), jnp.float32)
    _, y = jax.lax.scan(step, state0, (xh.transpose(1, 0, 2, 3),
                                       Bm.transpose(1, 0, 2),
                                       Cm.transpose(1, 0, 2),
                                       dt.transpose(1, 0, 2)))
    y = hold(y.transpose(1, 0, 2, 3).reshape(B, T, s["di"]))
    y = hold(_rms(hold(y * jax.nn.silu(z)), m["norm_w"], s["eps"]))
    return hold(mm(y, m["out_proj"]))


def _attention(s, hold, mm, h, a):
    B, T, _ = h.shape
    H, KV, Dh = s["H"], s["KV"], s["Dh"]
    q = hold(mm(h, a["wq"])).reshape(B, T, H, Dh)
    k = hold(mm(h, a["wk"])).reshape(B, T, KV, Dh)
    v = hold(mm(h, a["wv"])).reshape(B, T, KV, Dh)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * s["attn_mult"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, T, H * Dh)
    return hold(mm(att, a["wo"]))


def _moe(s, hold, mm, h, m):
    probs = jax.nn.softmax(h @ m["router"], axis=-1)            # (B, T, E)
    top, idx = jax.lax.top_k(probs, s["K"])
    gates = jnp.sum(jax.nn.one_hot(idx, s["E"]) * (top / top.sum(
        -1, keepdims=True))[..., None], axis=-2)[..., :s["held"]]

    def expert(e_w):
        g, u, o = e_w
        return hold(mm(jax.nn.silu(hold(mm(h, g))) * hold(mm(h, u)), o))

    outs = jax.lax.map(expert, (m["wi_gate"], m["wi_up"], m["wo"]))
    routed = hold(jnp.einsum("ebtd,bte->btd", outs, gates))
    sm = m["shared_mlp"]
    shared = hold(mm(jax.nn.silu(hold(mm(h, sm["wi_gate"])))
                     * hold(mm(h, sm["wi_up"])), sm["wo"]))
    return hold(routed + shared)


def _layer(s, quant, kind, x, lw):
    hold, mm = _ops(quant)
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    h = _rms(x, lw["ln1"], s["eps"])
    if kind == "mamba":
        out = _mamba(s, hold, mm, h, lw["mamba"])
    else:
        out = _attention(s, hold, mm, h, lw["attn"])
    x = hold(x + hold(s["res_mult"] * out))
    h = _rms(x, lw["ln2"], s["eps"])
    return hold(x + hold(s["res_mult"] * _moe(s, hold, mm, h, lw["moe"])))


def reference_logits(c: dict, w: dict, tokens, n_score: int,
                     quant: str = "none"):
    """Logits (B, n_score, V) in float32 at the last ``n_score`` positions
    of ``tokens`` (B, T), over the published vocabulary: the reference, or
    with ``quant="fp8"`` the control.  Call under
    ``jax.default_matmul_precision("highest")``."""
    s = sizes(c)
    hold, mm = _ops(quant)
    layers = w["layers"]
    layers = (layers,) if isinstance(layers, dict) else layers
    x = hold(w["embed"][tokens].astype(jnp.float32) * s["emb_mult"])
    for (kind, _, _), lw in zip(runs(s), layers):
        x, _ = jax.lax.scan(
            lambda x, l, kind=kind: (_layer(s, quant, kind, x, l), None),
            x, lw)
    x = _rms(x[:, -n_score:], w["final_norm"].astype(jnp.float32), s["eps"])
    head = w["lm_head"][:, :s["V"]].astype(jnp.float32)
    return hold(mm(x, head) / s["logit_div"])


# ---------------------------------------------------------------------------
# Work a step needs
# ---------------------------------------------------------------------------


def _counts(s) -> tuple:
    """(Mamba2 layers, attention layers)."""
    m = sum(k == "mamba" for k in s["kinds"])
    return m, s["L"] - m


def _mamba_params(s) -> int:
    """One Mamba2 mixer's projection weights (z, x, B, C, dt, out)."""
    D, di, N, Hm = s["D"], s["di"], s["N"], s["Hm"]
    return D * (2 * di + 2 * N + Hm) + di * D


def _attn_params(s) -> int:
    D, H, KV, Dh = s["D"], s["H"], s["KV"], s["Dh"]
    return D * H * Dh * 2 + D * KV * Dh * 2


def _held_rows(s, tokens: int) -> float:
    """Expected (token, expert) rows a layer's ``tokens`` tokens route to
    the experts held here, under uniform routing."""
    return tokens * s["K"] * s["held"] / s["E"]


def _held_touched(s, tokens: int) -> float:
    """Expected held experts a layer's ``tokens`` tokens route to, under
    uniform routing: held (1 - (1 - K/E)^tokens)."""
    return s["held"] * (1.0 - (1.0 - s["K"] / s["E"]) ** tokens)


def _token_flops(s, tokens: int) -> float:
    """FLOPs of ``tokens`` tokens through every layer's projections, conv,
    SSM recurrence (a state update, 3 H P N, and its read-out, 2 H P N, per
    token), router, held experts and shared expert; attention's scores
    are counted apart."""
    m, a = _counts(s)
    D, F, Fs, Hm, P, N = s["D"], s["F"], s["Fs"], s["Hm"], s["P"], s["N"]
    conv = s["conv"] * (s["di"] + 2 * N)
    mixer = (m * (2 * _mamba_params(s) + 2 * conv + 5 * Hm * P * N)
             + a * 2 * _attn_params(s))
    mlp = s["L"] * (2 * D * s["E"] + 2 * 3 * D * Fs)
    return (tokens * (mixer + mlp)
            + s["L"] * 2 * _held_rows(s, tokens) * 3 * D * F)


def _weight_bytes(s, tokens: int) -> float:
    """Weights a step over ``tokens`` tokens must read once: the mixers,
    norms, router, the held experts the tokens route to, the shared
    expert, the final norm and the LM head over the published vocabulary;
    of the embedding only the rows looked up."""
    b = DTYPE_BYTES[s["dtype"]]
    m, a = _counts(s)
    D, F, Fs, N = s["D"], s["F"], s["Fs"], s["N"]
    mamba_small = (s["conv"] + 1) * (s["di"] + 2 * N) * b + s["di"] * b \
        + 3 * s["Hm"] * 4
    mixers = m * (_mamba_params(s) * b + mamba_small) \
        + a * _attn_params(s) * b
    mlp = s["L"] * (_held_touched(s, tokens) * 3 * D * F * b
                    + D * s["E"] * ROUTER_BYTES + 3 * D * Fs * b
                    + 2 * D * b)
    return mixers + mlp + (D + D * s["V"]) * b + min(tokens, s["V"]) * D * b


def _state_bytes(s, batch: int) -> float:
    """One pass over the Mamba2 layers' decode state of ``batch`` lanes:
    the float32 SSM state and the conv state."""
    m, _ = _counts(s)
    conv = (s["conv"] - 1) * (s["di"] + 2 * s["N"]) * DTYPE_BYTES[s["dtype"]]
    return m * batch * (s["Hm"] * s["P"] * s["N"] * STATE_BYTES + conv)


def state_bytes(c: dict, batch: int) -> float:
    """What a decode step of ``batch`` lanes must move of the Mamba2
    layers' states: each read once and written once."""
    return 2.0 * _state_bytes(sizes(c), batch)


def _kv_bytes_per_position(s) -> int:
    _, a = _counts(s)
    return a * 2 * s["KV"] * s["Dh"] * DTYPE_BYTES[s["dtype"]]


def prefill_work(c: dict, batch: int, seq: int) -> tuple:
    """(FLOPs, bytes) of one prefill of ``batch`` prompts of ``seq`` tokens
    that returns last-position logits and fills the cache.  FLOPs: every
    token through every layer (``_token_flops``), causal attention counted
    as half of the full S x S, the LM head at the last position only.
    Bytes: weights once, the SSM and conv states and the K/V of every
    position written once, the logits written."""
    s = sizes(c)
    _, a = _counts(s)
    tokens = batch * seq
    flops = (_token_flops(s, tokens)
             + a * batch * 2 * 2 * s["H"] * s["Dh"] * seq * (seq + 1) / 2
             + batch * 2 * s["D"] * s["V"])
    b = DTYPE_BYTES[s["dtype"]]
    byts = (_weight_bytes(s, tokens) + _state_bytes(s, batch)
            + tokens * _kv_bytes_per_position(s) + batch * s["V"] * b)
    return float(flops), float(byts)


def decode_work(c: dict, batch: int, held: int) -> tuple:
    """(FLOPs, bytes) of one decode step of ``batch`` lanes, each holding
    ``held`` earlier positions.  FLOPs: one token per lane through every
    layer and the LM head, and attention over the held positions plus its
    own.  Bytes: weights once (of the held experts those the batch can
    touch), the SSM and conv states read and written once, the held K/V
    read once and the new position's written, the logits written."""
    s = sizes(c)
    _, a = _counts(s)
    flops = (_token_flops(s, batch)
             + batch * (a * 2 * 2 * s["H"] * s["Dh"] * (held + 1)
                        + 2 * s["D"] * s["V"]))
    b = DTYPE_BYTES[s["dtype"]]
    byts = (_weight_bytes(s, batch) + 2 * _state_bytes(s, batch)
            + batch * (held + 1) * _kv_bytes_per_position(s)
            + batch * s["V"] * b)
    return float(flops), float(byts)
