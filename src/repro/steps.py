"""Step functions (train / prefill / decode) + dry-run input specs.

These are the units the launcher runs and the dry-run lowers: every
(architecture x shape x mesh) cell resolves to one jitted function here,
with in/out shardings from ``ShardingRules``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig, OptimizerConfig, ShapeSpec
from .models import transformer as T
from .optim import adamw_init, adamw_update
from .parallel import ShardingRules


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def batch_shapes(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Abstract input batch for a cell (the assignment's ``input_specs``)."""
    B = shape.global_batch
    sd = jax.ShapeDtypeStruct
    if shape.kind == "train":
        S = shape.seq_len
        batch: Dict[str, Any] = {
            "tokens": sd((B, S), jnp.int32),
            "targets": sd((B, S), jnp.int32),
        }
    elif shape.kind == "prefill":
        S = shape.seq_len
        batch = {"tokens": sd((B, S), jnp.int32)}
    else:  # decode: one new token (the cache is a separate argument)
        batch = {"tokens": sd((B, 1), jnp.int32)}
        return batch
    if cfg.frontend == "vision_stub":
        # patches replace the leading part of the context window
        batch["tokens"] = sd((B, S - cfg.n_patches), jnp.int32)
        if "targets" in batch:
            batch["targets"] = sd((B, S - cfg.n_patches), jnp.int32)
        batch["patches"] = sd((B, cfg.n_patches, cfg.frontend_dim),
                              jnp.dtype(cfg.dtype))
    if cfg.frontend == "audio_stub":
        batch["frames"] = sd((B, S // cfg.enc_seq_divisor, cfg.frontend_dim),
                             jnp.dtype(cfg.dtype))
    return batch


def decode_state_shapes(cfg: ModelConfig, shape: ShapeSpec):
    """Abstract KV/SSM cache for a decode cell (seq_len tokens resident)."""
    B = shape.global_batch
    enc_len = shape.seq_len // cfg.enc_seq_divisor if cfg.is_encdec else 0
    return T.cache_shapes(cfg, B, shape.seq_len, enc_len)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    rules: Optional[ShardingRules] = None,
                    remat: bool = True, donate: bool = True,
                    microbatches: int = 1, accum_dtype=jnp.float32):
    """Returns train_step(params, opt_state, batch) ->
        (params, opt_state, metrics).

    ``microbatches > 1`` runs gradient accumulation over batch splits
    (lax.scan): peak activation / MoE-dispatch memory divides by the
    microbatch count at the cost of re-streaming the weights per
    microbatch - the standard lever when a cell exceeds HBM."""
    sc = rules.constrain if rules is not None else (lambda x, kind=None: x)

    def loss_fn(params, batch):
        return T.forward_train(cfg, params, batch, sc=sc, remat=remat)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _pin(grads, params):
        """Constrain gradients to the parameter shardings: keeps the
        backward scan's dxs accumulators sharded (H-M3, section Perf)."""
        if rules is None:
            return grads
        specs = rules.param_specs(params)
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, rules.sharding(s)), grads, specs)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = _pin(grads, params)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches,
                                     x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)

            def acc(carry, b):
                gsum, lsum = carry
                (l, m), g = grad_fn(params, b)
                g = _pin(g, params)
                gsum = jax.tree.map(
                    lambda a, x: a + x.astype(accum_dtype), gsum, g)
                return (gsum, lsum + l), m

            (gsum, lsum), ms = jax.lax.scan(
                acc, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics = jax.tree.map(lambda v: jnp.mean(v), ms)
            metrics["loss"] = loss
        new_params, new_opt, opt_metrics = adamw_update(
            grads, opt_state, params, opt_cfg)
        metrics = {**metrics, **opt_metrics}
        return new_params, new_opt, metrics

    return train_step


def make_prefill(cfg: ModelConfig, max_len: int,
                 rules: Optional[ShardingRules] = None):
    sc = rules.constrain if rules is not None else (lambda x, kind=None: x)

    def prefill_step(params, batch):
        return T.prefill(cfg, params, batch, max_len=max_len, sc=sc)

    return prefill_step


def make_decode_step(cfg: ModelConfig,
                     rules: Optional[ShardingRules] = None):
    sc = rules.constrain if rules is not None else (lambda x, kind=None: x)

    def serve_step(params, caches, tokens):
        return T.decode_step(cfg, params, caches, tokens, sc=sc)

    return serve_step


def init_train_state(cfg: ModelConfig, key):
    """Materialized params + optimizer state (small configs / real runs)."""
    params = T.init_params(cfg, key)
    return params, adamw_init(params)


def train_state_shapes(cfg: ModelConfig):
    params = T.param_shapes(cfg)
    opt = jax.eval_shape(adamw_init, params)
    return params, opt
