"""Pure-JAX model zoo covering the ten assigned architectures."""

from .transformer import (decode_step, forward_hidden, forward_train,
                          init_cache, init_params, lm_logits, param_shapes,
                          cache_shapes, prefill)

__all__ = [
    "cache_shapes",
    "decode_step",
    "forward_hidden",
    "forward_train",
    "init_cache",
    "init_params",
    "lm_logits",
    "param_shapes",
    "prefill",
]
