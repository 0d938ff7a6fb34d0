"""Per-architecture smoke + decode-vs-teacher-forcing consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import (decode_step, forward_hidden, forward_train,
                          init_params, lm_logits, prefill)

KEY = jax.random.key(0)


def _batch(cfg, B, S, key, dtype=jnp.float32, with_targets=True):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if with_targets:
        batch["targets"] = jax.random.randint(key, (B, S), 0,
                                              cfg.vocab_size)
    if cfg.frontend == "vision_stub":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.frontend_dim), dtype)
    if cfg.frontend == "audio_stub":
        batch["frames"] = jax.random.normal(
            key, (B, S // cfg.enc_seq_divisor, cfg.frontend_dim), dtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_shapes_and_finite(arch):
    """Assignment requirement: reduced config, one forward/train step on
    CPU, output shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    params = init_params(cfg, KEY)
    batch = _batch(cfg, 2, 32, KEY, jnp.dtype(cfg.dtype))
    loss, metrics = forward_train(cfg, params, batch, remat=False)
    assert np.isfinite(float(loss))
    grads = jax.grad(
        lambda p: forward_train(cfg, p, batch, remat=True)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, KEY)
    B, S = 2, 32
    batch = _batch(cfg, B, S, KEY, jnp.dtype(cfg.dtype), with_targets=False)
    logits, cache = prefill(cfg, params, batch, max_len=S + 8)
    assert logits.shape[0] == B and logits.shape[1] == 1
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    logits2, cache = decode_step(cfg, params, cache, tok)
    assert logits2.shape[:2] == (B, 1)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


def _full_logits(cfg, params, batch):
    hidden, _, _ = forward_hidden(cfg, params, batch, remat=False)
    return lm_logits(cfg, params, hidden)


def _decode_errors(cfg, B=2, S=24, EXTRA=4):
    """prefill S tokens, decode EXTRA more one at a time: each step's
    largest logit gap to the full forward at that position, and the last
    cache."""
    params = init_params(cfg, jax.random.key(1))
    toks = jax.random.randint(jax.random.key(2), (B, S + EXTRA), 0,
                              cfg.vocab_size)
    bf = {"tokens": toks}
    bp = {"tokens": toks[:, :S]}
    if cfg.frontend == "vision_stub":
        pat = jax.random.normal(KEY, (B, cfg.n_patches, cfg.frontend_dim))
        bf["patches"] = pat
        bp["patches"] = pat
    if cfg.frontend == "audio_stub":
        fr = jax.random.normal(
            KEY, (B, (S + EXTRA) // cfg.enc_seq_divisor, cfg.frontend_dim))
        bf["frames"] = fr
        bp["frames"] = fr
    ref = np.asarray(_full_logits(cfg, params, bf), np.float32)
    off = cfg.n_patches if cfg.frontend == "vision_stub" else 0
    logits, cache = prefill(cfg, params, bp, max_len=S + EXTRA + off)
    errs = [np.abs(np.asarray(logits[:, 0], np.float32)
                   - ref[:, off + S - 1]).max()]
    for t in range(EXTRA):
        logits, cache = decode_step(cfg, params, cache,
                                    toks[:, S + t][:, None])
        errs.append(np.abs(np.asarray(logits[:, 0], np.float32)
                           - ref[:, off + S + t]).max())
    return errs, cache


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "zamba2-2.7b", "rwkv6-7b",
                                  "whisper-base", "mixtral-8x7b",
                                  "internvl2-2b", "granite-moe-1b-a400m",
                                  "granite-4.0-h-small"])
def test_decode_matches_teacher_forcing(arch):
    """prefill+decode logits == full forward at the same positions
    (catches cache/rope/state bugs)."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    errs, _ = _decode_errors(cfg)
    assert max(errs) < 1e-4, errs


@pytest.mark.parametrize("impl", ["auto", "interpret"])
@pytest.mark.parametrize("arch,over,rings", [
    # granite's head dim: half a lane tile, the case the layout is for
    ("granite-moe-1b-a400m", {"d_head": 64}, ("layers",)),
    # a sliding-window ring the prompt already wraps
    ("mixtral-8x7b", {"sliding_window": 16}, ("layers",)),
    ("zamba2-2.7b", {}, ("shared",)),               # the shared block's ring
    ("whisper-base", {}, ("layers", "cross")),       # and the cross cache
])
def test_decode_over_lane_dense_rings(arch, over, rings, impl, monkeypatch):
    """Every K/V buffer of the cache is lane-dense, (L, B, T, kv*dh), and
    decode over it gives the cache-free forward's logits, through the
    oracle a CPU takes (``auto``) and through the TPU's Pallas kernel
    (interpreted): its reading of the stacked rings by layer index, of the
    new tokens and of the masks is the model's."""
    import functools

    from repro.kernels.ring_decode import ops
    from repro.models import layers

    monkeypatch.setattr(layers, "ring_decode",
                        functools.partial(ops.ring_decode, impl=impl))
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32", **over)
    errs, cache = _decode_errors(cfg, EXTRA=8)
    for name in rings:
        for buf in (cache[name]["k"], cache[name]["v"]):
            assert buf.ndim == 4, (name, buf.shape)
            assert buf.shape[3] == cfg.n_kv_heads * cfg.head_dim
    if cfg.sliding_window:
        assert cache["layers"]["k"].shape[2] == cfg.sliding_window
    assert max(errs) < 1e-4, errs


@pytest.mark.parametrize("step", ["eager", "donated"])
def test_sliding_window_ring_buffer(step):
    """SWA decode far beyond the window uses the ring buffer correctly:
    logits must keep matching teacher forcing past the wrap point, also
    through the jitted step that takes its cache (donated) and writes the
    new token into it in place."""
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"),
                              dtype="float32", sliding_window=16)
    params = init_params(cfg, jax.random.key(3))
    B, S, EXTRA = 1, 24, 12   # wraps a window of 16
    toks = jax.random.randint(jax.random.key(4), (B, S + EXTRA), 0,
                              cfg.vocab_size)
    ref = np.asarray(_full_logits(cfg, params, {"tokens": toks}),
                     np.float32)
    logits, cache = prefill(cfg, params, {"tokens": toks[:, :S]},
                            max_len=S + EXTRA)
    dec = (decode_step if step == "eager" else
           jax.jit(decode_step, static_argnums=0, donate_argnums=2))
    errs = []
    for t in range(EXTRA):
        old = cache
        logits, cache = dec(cfg, params, cache, toks[:, S + t][:, None])
        assert old["layers"]["k"].is_deleted() == (step == "donated")
        errs.append(np.abs(np.asarray(logits[:, 0], np.float32)
                           - ref[:, S + t]).max())
    assert max(errs) < 1e-4, errs


def test_full_configs_match_assignment():
    """Exact published hyperparameters (the assigned table)."""
    spec = {
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
        "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "rwkv6-7b": (32, 4096, 0, 0, 14336, 65536),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        # one period of 10 layers of the published 40 (the chip's share)
        "granite-4.0-h-small": (10, 4096, 32, 8, 768, 100352),
    }
    for arch, (L_, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.moe_d_ff or cfg.d_ff, cfg.vocab_size)
        assert got == (L_, d, h, kv, ff, v), (arch, got)
    assert get_config("zamba2-2.7b").ssm_state == 64
    assert get_config("mixtral-8x7b").n_experts == 8
    assert get_config("mixtral-8x7b").n_experts_active == 2
    assert get_config("granite-moe-1b-a400m").n_experts == 32
    assert get_config("granite-moe-1b-a400m").n_experts_active == 8
    assert get_config("mixtral-8x7b").sliding_window == 4096
    g4 = get_config("granite-4.0-h-small")
    assert (g4.n_experts, g4.experts_held, g4.n_experts_active,
            g4.shared_d_ff) == (72, 9, 10, 1536)
    assert (g4.ssm_heads, g4.ssm_head_dim, g4.ssm_state, g4.ssm_conv,
            g4.head_dim, g4.rope) == (128, 64, 128, 4, 128, False)
    assert g4.layer_kinds() == ("mamba2_moe",) * 5 + ("moe",) \
        + ("mamba2_moe",) * 4
    assert (g4.embedding_multiplier, g4.attention_multiplier,
            g4.residual_multiplier, g4.logits_scaling) \
        == (12.0, 0.0078125, 0.22, 16.0)
    from repro.configs.granite_4_0_h_small import PUBLISHED
    assert PUBLISHED.n_layers == 40 and PUBLISHED.n_experts_held == 72
    assert [i for i, k in enumerate(PUBLISHED.layer_kinds())
            if k == "moe"] == [5, 15, 25, 35]
