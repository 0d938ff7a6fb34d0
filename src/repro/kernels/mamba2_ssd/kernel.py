"""Mamba2 SSD (chunked state-space scan) as a Pallas TPU kernel.

TPU adaptation: the recurrent state (P x N per head) lives in VMEM scratch
and persists across the *sequential* chunk axis of the grid (Pallas TPU
executes grid iterations in row-major order on a core, so a
(batch*heads, chunks) grid gives exactly the chunk-major scan the SSD
algorithm needs - the carry never touches HBM).  Per chunk the kernel does
three MXU contractions:

  scores   = C_chunk @ B_chunk^T              (Q x Q, masked by decay L)
  y_diag   = (L o scores) @ X_chunk           (intra-chunk)
  y_off    = C_chunk @ state * decay          (inter-chunk)
  state    = chunk_decay * state + B^T @ (X * decay_to_end)

Block shapes: Q=128 rows (sublane-tiled); P and N blocks span their whole
dim, which the TPU tiling accepts; the log-decays come in as a (1, Q) row.
Mosaic lowers no cumsum, so the decay prefix sums are products with a
triangular ones matrix at HIGHEST precision.  VMEM per program:
~(3*Q*N + Q*P + 2*Q*Q + P*N) f32 ~ 320 KiB at Q=128, P=N=64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _tril(n):
    """(n, n) f32 lower-triangular ones, diagonal included."""
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (i >= j).astype(jnp.float32)


def _ssd_kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    x = xdt_ref[...].astype(f32)                  # (Q, P)
    a = a_ref[...].astype(f32)                    # (1, Q) row
    Bm = b_ref[...].astype(f32)                   # (Q, N)
    Cm = c_ref[...].astype(f32)                   # (Q, N)

    # inclusive cumsum of a as a column and as a row: Mosaic has no cumsum,
    # so both are products with the triangular ones matrix
    tril = _tril(chunk)
    nt = (((1,), (1,)), ((), ()))
    a_cum = jax.lax.dot_general(tril, a, nt, precision=hi,
                                preferred_element_type=f32)      # (Q, 1)
    a_cum_row = jax.lax.dot_general(a, tril, nt, precision=hi,
                                    preferred_element_type=f32)  # (1, Q)
    a_total = jnp.sum(a, axis=1, keepdims=True)   # (1, 1)
    # L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(tril > 0, jnp.exp(a_cum - a_cum_row), 0.0)    # (Q, Q)

    scores = jax.lax.dot_general(
        Cm, Bm, nt, preferred_element_type=f32)   # (Q, Q)
    y_diag = jax.lax.dot_general(
        L * scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)               # (Q, P)

    state = state_ref[...]                        # (N, P)
    y_off = jax.lax.dot_general(
        Cm, state, (((1,), (0,)), ((), ())),
        preferred_element_type=f32) * jnp.exp(a_cum)

    # state update: state' = exp(a_total) * state + B^T @ (x * decay_to_end)
    decay_to_end = jnp.exp(a_total - a_cum)       # (Q, 1)
    upd = jax.lax.dot_general(
        Bm * decay_to_end, x, (((0,), (0,)), ((), ())),
        preferred_element_type=f32)               # (N, P)
    new_state = jnp.exp(a_total) * state + upd
    state_ref[...] = new_state

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)
    state_out_ref[...] = new_state.astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_fwd(xdt, a, Bm, Cm, *, chunk: int = DEFAULT_CHUNK,
            interpret: bool = False):
    """xdt: (B,S,H,P); a: (B,S,H); Bm,Cm: (B,S,N) (shared across heads).

    Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    B, S, H, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk

    # fold (B,H) into the grid's leading axis; B/C are indexed by g // H
    # (shared across the head sub-axis)
    # a gets a unit middle axis so its (1, chunk) block is lane-tiled
    xf = xdt.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    af = a.transpose(0, 2, 1).reshape(B * H, 1, S)
    grid = (B * H, n_chunks)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, states = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, 1, chunk), lambda g, c: (g, 0, c)),
            pl.BlockSpec((None, chunk, N), lambda g, c: (g // H, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda g, c: (g // H, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((None, N, P), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), xdt.dtype),
            jax.ShapeDtypeStruct((B * H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xf, af, Bm, Cm)
    y = y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    # states: (B*H, N, P) -> (B, H, P, N)
    states = states.reshape(B, H, N, P).transpose(0, 1, 3, 2)
    return y, states
