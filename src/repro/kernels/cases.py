"""Each Pallas kernel at the widths of the configuration it was written for.

The TPU compile tests (tests/test_tpu_compile.py) compile these shapes for
a described v5e, and ``chip_smoke.py`` runs them on the chip against the
``ref.py`` oracles.  ``moe_ragged_gmm`` and ``moe_ragged_gmm_mixtral``
are the dropless MoE's grouped matmul; ``moe_combine_granite`` and
``moe_combine_granite4h`` its combine at the MoE cells' prefill (32 lanes
and 16 of 1024 tokens); ``ring_decode_granite`` and ``ring_decode_qwen3``
the decode step's attention over the K/V ring at the benchmark cells' lanes
and rings.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from ..configs import get_config
from .flash_attention.ops import flash_attention
from .flash_attention.ref import attention_ref
from .mamba2_ssd.ops import ssd
from .mamba2_ssd.ref import ssd_ref
from .moe_combine.kernel import TILE
from .moe_combine.ops import group_rows, moe_combine
from .moe_combine.ref import moe_combine_ref
from .moe_gmm.ops import ragged_matmul
from .moe_gmm.ref import ragged_ref
from .ring_decode.ops import ring_decode
from .ring_decode.ref import ring_decode_ref
from .rwkv6_wkv.ops import wkv
from .rwkv6_wkv.ref import wkv_ref


@dataclass(frozen=True)
class KernelCase:
    name: str
    op: Callable          # op(*args, impl=...)
    ref: Callable         # ref(*args), the oracle
    shapes: Tuple[Tuple[Tuple[int, ...], str], ...]   # (shape, dtype) per arg
    draw: Callable        # draw(key, shapes) -> args in the op's domain

    def abstract(self, sharding=None):
        return [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sharding)
                for s, d in self.shapes]

    def sample(self, key):
        return self.draw(key, self.shapes)


def _normal(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def real_width_cases() -> Tuple[KernelCase, ...]:
    qwen = get_config("qwen3-0.6b")
    zamba = get_config("zamba2-2.7b")
    rwkv = get_config("rwkv6-7b")
    mixtral = get_config("mixtral-8x7b")
    granite = get_config("granite-moe-1b-a400m")
    granite4h = get_config("granite-4.0-h-small")

    # flash attention: qwen3-0.6b heads at a 4k context
    qkv = ((1, 4096, qwen.n_heads, qwen.head_dim), "bfloat16")

    def flash_draw(key, shapes):
        return [_normal(k, *sd) for k, sd in zip(jax.random.split(key, 3),
                                                  shapes)]

    # SSD: zamba2-2.7b's Mamba2 heads, chunk 128
    S, H, P, N = 4096, zamba.ssm_heads, zamba.ssm_head_dim, zamba.ssm_state
    ssd_shapes = (((1, S, H, P), "float32"), ((1, S, H), "float32"),
                  ((1, S, N), "float32"), ((1, S, N), "float32"))

    def ssd_draw(key, shapes):
        k = jax.random.split(key, 4)
        return [_normal(k[0], *shapes[0], 0.5),
                -jnp.abs(_normal(k[1], *shapes[1], 0.1)),
                _normal(k[2], *shapes[2], 0.5),
                _normal(k[3], *shapes[3], 0.5)]

    # WKV: rwkv6-7b's heads, chunk 16
    Hr, Pr = rwkv.rwkv_heads, rwkv.rwkv_head_dim
    rkvw = ((1, 1024, Hr, Pr), "float32")

    def wkv_draw(key, shapes):
        k = jax.random.split(key, 5)
        r, kk, v = (_normal(k[i], *shapes[i]) for i in range(3))
        w = jnp.exp(-jnp.exp(_normal(k[3], *shapes[3], 0.5) - 2))
        return [r, kk, v, w, _normal(k[4], *shapes[4], 0.1)]

    # ragged grouped matmul: granite-moe-1b-a400m's gate projection over a
    # 1024-token prompt's rows (k per token), routed to the first three
    # quarters of the experts (the rest empty)
    Eg, Dg, Fg = granite.n_experts, granite.d_model, granite.moe_d_ff
    ragged_shapes = (((1024 * granite.n_experts_active, Dg), "bfloat16"),
                     ((Eg, Dg, Fg), "bfloat16"), ((Eg,), "int32"))

    # and mixtral-8x7b's, over a 1024-token prompt's rows (k = 2 per token)
    Em, Dm, Fm = mixtral.n_experts, mixtral.d_model, mixtral.moe_d_ff
    mixtral_shapes = (((1024 * mixtral.n_experts_active, Dm), "bfloat16"),
                      ((Em, Dm, Fm), "bfloat16"), ((Em,), "int32"))

    def ragged_draw(key, shapes):
        k = jax.random.split(key, 3)
        (M, _), _, (E,) = (s for s, _ in shapes)
        experts = jax.random.randint(k[2], (M,), 0, max(1, 3 * E // 4))
        sizes = jnp.bincount(experts, length=E).astype(jnp.int32)
        return [_normal(k[0], *shapes[0]), _normal(k[1], *shapes[1], 0.05),
                sizes]

    # the MoE combine over a prefill's rows: a uniform top-k routing, the
    # rows past the held experts' (never computed) NaN
    def combine_draw(key, shapes, n_experts):
        (R, D), (T, k) = shapes[0][0], shapes[1][0]
        held = shapes[3][0][1]
        ks = jax.random.split(key, 3)
        experts = jnp.argsort(jax.random.uniform(ks[0], (T, n_experts)),
                              axis=1)[:, :k].astype(jnp.int32)
        counts, pos, bounds = group_rows(experts, held)
        gates = jax.random.uniform(ks[1], (T, k), jnp.float32)
        ys = _normal(ks[2], (R, D), jnp.bfloat16)
        ys = jnp.where(jnp.arange(R)[:, None] < counts.sum(), ys, jnp.nan)
        return [ys, pos, gates / gates.sum(1, keepdims=True), bounds]

    def combine_case(name, cfg, tokens):
        k, D, held = cfg.n_experts_active, cfg.d_model, cfg.n_experts_held
        shapes = (((tokens * k, D), "bfloat16"), ((tokens, k), "int32"),
                  ((tokens, k), "float32"),
                  ((-(-tokens // TILE) + 1, held), "int32"))
        # the slab table is the kernel's alone
        return KernelCase(name, moe_combine,
                          lambda ys, pos, gates, _: moe_combine_ref(
                              ys, pos, gates), shapes,
                          functools.partial(combine_draw,
                                            n_experts=cfg.n_experts))

    # decode attention over one ring of a stack of two, as the benchmark
    # cells hold it: 1024-token prompts + 128 tokens out in a 1168-slot ring
    # (1156 rounded to whole tiles), one new token at 15/16 of the ring
    def ring_draw(key, shapes):
        k = jax.random.split(key, 5)
        (R, T), pos = shapes[4][0], shapes[4][0][1] * 15 // 16
        valid = jnp.broadcast_to(jnp.arange(T) <= pos, (R, T))
        return [_normal(k[0], *shapes[0]), _normal(k[1], *shapes[1]),
                _normal(k[2], *shapes[2]), jnp.int32(1),
                valid.astype(jnp.int32), _normal(k[3], *shapes[5]),
                _normal(k[4], *shapes[6]), jnp.asarray([pos], jnp.int32)]

    def ring_case(name, cfg, lanes):
        R, C, T = cfg.n_heads, cfg.n_kv_heads * cfg.head_dim, 1168
        shapes = (((lanes, R, C), "bfloat16"),
                  ((2, lanes, T, C), "bfloat16"), ((2, lanes, T, C), "bfloat16"),
                  ((), "int32"), ((R, T), "int32"),
                  ((lanes, 1, C), "bfloat16"), ((lanes, 1, C), "bfloat16"),
                  ((1,), "int32"))
        scale = 1.0 / math.sqrt(cfg.head_dim)
        return KernelCase(name, functools.partial(ring_decode, scale=scale),
                          functools.partial(ring_decode_ref, scale=scale),
                          shapes, ring_draw)

    # each op and oracle at its defaults: causal attention, SSD chunk 128,
    # WKV chunk 16
    return (
        KernelCase("flash_attention", flash_attention, attention_ref,
                   (qkv,) * 3, flash_draw),
        KernelCase("mamba2_ssd", ssd, ssd_ref, ssd_shapes, ssd_draw),
        KernelCase("rwkv6_wkv", wkv, wkv_ref,
                   (rkvw,) * 4 + (((Hr, Pr), "float32"),), wkv_draw),
        KernelCase("moe_ragged_gmm", ragged_matmul, ragged_ref,
                   ragged_shapes, ragged_draw),
        KernelCase("moe_ragged_gmm_mixtral", ragged_matmul, ragged_ref,
                   mixtral_shapes, ragged_draw),
        combine_case("moe_combine_granite", granite, 32 * 1024),
        combine_case("moe_combine_granite4h", granite4h, 16 * 1024),
        ring_case("ring_decode_granite", granite, 32),
        ring_case("ring_decode_qwen3", qwen, 16),
    )
