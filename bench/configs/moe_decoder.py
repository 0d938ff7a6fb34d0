"""Decoder-only transformers with a top-k mixture-of-experts MLP: the
weights, the plain reference and the work counts of ``decoder.py``, with a
lower-precision control that rounds wherever a bfloat16 program rounds.

``decoder.py``'s control rounds only the operands of the weight matmuls to
float8_e4m3 and keeps every other value in float32, so it holds the
residual stream, the K/V, the attention and expert outputs and the logits
more precisely than the bfloat16 program it is set against.  For a dense
model that control still reads far above the program.  For a top-k MoE it
does not: the program's own rounding swaps near-tied experts, and the two
readings overlap.  Here ``quant="fp8"`` computes the reference in
float8_e4m3 at every point where the program holds a value in bfloat16:
the embedding, the residual stream after each add, q, k and v, each
projection's and expert's output, the combined MoE output and the logits,
besides the operands of every weight matmul.  Scales as in ``decoder.py``:
one per token for activations, one per output channel for weights.  The
router stays in float32, as in the program.  ``quant="none"`` is
``decoder.py``'s reference unchanged.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_moe_decoder_base", Path(__file__).with_name("decoder.py"))
decoder = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(decoder)

sizes = decoder.sizes
program_expect = decoder.program_expect
make_weights = decoder.make_weights
prefill_work = decoder.prefill_work
decode_work = decoder.decode_work


def _held(x):
    """x as float8_e4m3 holds it, one scale per token (last axis)."""
    return decoder._fp8(x, -1)


def _mm(x, w):
    return decoder._matmul(x, w, "fp8")


def _layer_fp8(s, x, lw):
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    B, T, _ = x.shape
    H, KV, Dh = s["H"], s["KV"], s["Dh"]
    a = lw["attn"]
    h = decoder._rms(x, lw["ln1"], s["eps"])
    q = _held(_mm(h, a["wq"])).reshape(B, T, H, Dh)
    k = _held(_mm(h, a["wk"])).reshape(B, T, KV, Dh)
    v = _held(_mm(h, a["wv"])).reshape(B, T, KV, Dh)
    if s["qk_norm"]:
        q = decoder._rms(q, a["q_norm"], s["eps"])
        k = decoder._rms(k, a["k_norm"], s["eps"])
    q = _held(decoder._rope(q, s["theta"]))
    k = _held(decoder._rope(k, s["theta"]))
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) * s["attn_mult"]
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, T, H * Dh)
    x = _held(x + _held(s["res_mult"] * _held(_mm(att, a["wo"]))))

    h = decoder._rms(x, lw["ln2"], s["eps"])
    m = lw["moe"]
    probs = jax.nn.softmax(h @ m["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, s["K"])
    gates = jnp.sum(jax.nn.one_hot(idx, s["E"]) * (top / top.sum(
        -1, keepdims=True))[..., None], axis=-2)

    def expert(e_w):
        g, u, o = e_w
        return _held(_mm(jax.nn.silu(_held(_mm(h, g))) * _held(_mm(h, u)),
                         o))

    outs = jax.lax.map(expert, (m["wi_gate"], m["wi_up"], m["wo"]))
    mlp = _held(jnp.einsum("ebtd,bte->btd", outs, gates))
    return _held(x + _held(s["res_mult"] * mlp)), None


def reference_logits(c: dict, w: dict, tokens, n_score: int,
                     quant: str = "none"):
    """Logits (B, n_score, V) at the last ``n_score`` positions of
    ``tokens`` (B, T): ``decoder.py``'s float32 reference, or with
    ``quant="fp8"`` the control described above.  Call under
    ``jax.default_matmul_precision("highest")``."""
    if quant == "none":
        return decoder.reference_logits(c, w, tokens, n_score)
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    s = sizes(c)
    x = _held(w["embed"][tokens].astype(jnp.float32) * s["emb_mult"])
    x, _ = jax.lax.scan(lambda x, lw: _layer_fp8(s, x, lw), x, w["layers"])
    x = decoder._rms(x[:, -n_score:], w["final_norm"].astype(jnp.float32),
                     s["eps"])
    head = w["lm_head"][:, :s["V"]].astype(jnp.float32)
    return _held(_mm(x, head) / s["logit_div"])
