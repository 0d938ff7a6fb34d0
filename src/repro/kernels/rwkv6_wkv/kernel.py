"""RWKV6 WKV recurrence (data-dependent per-channel decay) as a Pallas
TPU kernel.

TPU adaptation: like the SSD kernel, the (P x P) per-head state persists in
VMEM scratch across the sequential chunk axis.  Within a chunk the
intra-chunk quadratic form is evaluated through decay-scaled r~/k~ factors
(kept in f32; chunk=16 bounds the within-chunk decay range so the factors
stay representable - see models/rwkv6.py MAX_DECAY_RATE).

Mosaic lowers no cumsum: the decay prefix sums are products with a
triangular ones matrix at HIGHEST precision.

Per (batch*head, chunk) program:
  r,k,v,w tiles (Q, P);  state (P, P) f32 scratch;
  scores = tril(r~ @ k~^T, -1) + bonus diag; y = scores @ v + r~ @ state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 16


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, state_out_ref,
                state_ref, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    r = r_ref[...].astype(f32)                  # (Q, P)
    k = k_ref[...].astype(f32)
    v = v_ref[...].astype(f32)
    w = w_ref[...].astype(f32)
    u = u_ref[...].astype(f32)                  # (1, P)

    logw = jnp.log(jnp.maximum(w, 1e-8))
    # inclusive cumsum over the chunk (Mosaic has no cumsum): tril @ logw
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    cum = jax.lax.dot_general(
        (li >= lj).astype(f32), logw, (((1,), (0,)), ((), ())),
        precision=hi, preferred_element_type=f32)        # (Q, P)
    b_incl = jnp.exp(cum)
    b_excl = jnp.exp(cum - logw)
    # whole-chunk decay per k channel, as a column: logw^T @ ones
    b_last = jnp.exp(jax.lax.dot_general(
        logw, jnp.ones((chunk, 1), f32), (((0,), (0,)), ((), ())),
        precision=hi, preferred_element_type=f32))       # (P, 1)

    r_t = r * b_excl
    k_t = k / jnp.maximum(b_incl, 1e-37)

    scores = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())),
        preferred_element_type=f32)             # (Q, Q)
    scores = jnp.where(li > lj, scores, 0.0)    # strictly lower
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (Q, 1) bonus, j == i

    y = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    y = y + diag * v

    state = state_ref[...]                      # (P, P) [k_dim, v_dim]
    y = y + jax.lax.dot_general(
        r_t, state, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)

    upd = jax.lax.dot_general(
        k_t, v, (((0,), (0,)), ((), ())),
        preferred_element_type=f32)             # (P, P)
    new_state = (state + upd) * b_last
    state_ref[...] = new_state

    y_ref[...] = y.astype(y_ref.dtype)
    state_out_ref[...] = new_state.astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_fwd(r, k, v, w, u, *, chunk: int = DEFAULT_CHUNK,
            interpret: bool = False):
    """r,k,v,w: (B,S,H,P); u: (H,P) -> (y (B,S,H,P), state (B,H,P,P))."""
    B, S, H, P = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, P)

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.broadcast_to(u[None], (B, H, P)).reshape(B * H, 1, P)

    grid = (B * H, n_chunks)
    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y, states = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, 1, P), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, P, P), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), r.dtype),
            jax.ShapeDtypeStruct((B * H, P, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    states = states.reshape(B, H, P, P)
    return y, states
