"""Mamba2 (SSD - state-space duality) block, pure JAX.

Implements the chunked SSD algorithm of Mamba2 [arXiv:2405.21060]:
intra-chunk computation is an attention-like quadratic form over the chunk,
inter-chunk state is carried through a (small) chunk-level recurrence.  This
is the parallel training/prefill path; ``decode_step`` is the O(1) recurrent
update used for serving.  The Pallas kernel in ``repro.kernels.mamba2_ssd``
implements the same chunked dataflow with explicit VMEM tiling; this module
is also its reference oracle.

Shapes (per block):
  x        (B, S, d_model)
  d_inner  = expand * d_model;  heads H = d_inner / head_dim(P);  state N.
  in_proj  -> z (d_inner), xin (d_inner), B (N), C (N), dt (H)
  SSM state (B, H, P, N)
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, rms_norm

CHUNK = 256


def mamba2_params(key, d_model: int, d_inner: int, n_state: int,
                  n_heads: int, conv_k: int, dtype) -> Dict:
    # Projections are kept separate (z/x on the TP-sharded inner width;
    # B/C/dt small and replicated) so the tensor-parallel sharding rules in
    # repro.parallel.sharding map cleanly without resharding splits.
    ks = jax.random.split(key, 9)
    return {
        "w_z": dense_init(ks[0], d_model, d_inner, dtype),
        "w_x": dense_init(ks[1], d_model, d_inner, dtype),
        "w_B": dense_init(ks[2], d_model, n_state, dtype),
        "w_C": dense_init(ks[3], d_model, n_state, dtype),
        "w_dt": dense_init(ks[4], d_model, n_heads, dtype),
        "conv_x_w": (jax.random.normal(ks[5], (conv_k, d_inner), jnp.float32)
                     * 0.1).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_B_w": (jax.random.normal(ks[6], (conv_k, n_state), jnp.float32)
                     * 0.1).astype(dtype),
        "conv_B_b": jnp.zeros((n_state,), dtype),
        "conv_C_w": (jax.random.normal(ks[7], (conv_k, n_state), jnp.float32)
                     * 0.1).astype(dtype),
        "conv_C_b": jnp.zeros((n_state,), dtype),
        "A_log": jnp.zeros((n_heads,), jnp.float32),      # A = -exp(A_log)
        "dt_bias": jnp.full((n_heads,), -2.0, jnp.float32),
        "D": jnp.ones((n_heads,), jnp.float32),
        "norm_w": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(ks[8], d_inner, d_model, dtype),
    }


def _segsum(a: jnp.ndarray) -> jnp.ndarray:
    """a: (..., T) -> (..., T, T): out[i,j] = sum_{k=j+1..i} a[k] (i>=j)."""
    T = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, diff, -jnp.inf)


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv1d.  x: (B,S,C); w: (K,C); state: (B,K-1,C)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else state
    return jax.nn.silu(out), new_state


def _convs(p: Dict, xin, Bmat, Cmat, state: Dict):
    """The three depthwise causal convs, from ``state`` ({} : zeros).
    Returns (x, B, C, new conv state)."""
    xin, cs_x = _causal_conv(xin, p["conv_x_w"], p["conv_x_b"],
                             state.get("x"))
    Bmat, cs_B = _causal_conv(Bmat, p["conv_B_w"], p["conv_B_b"],
                              state.get("B"))
    Cmat, cs_C = _causal_conv(Cmat, p["conv_C_w"], p["conv_C_b"],
                              state.get("C"))
    return xin, Bmat, Cmat, {"x": cs_x, "B": cs_B, "C": cs_C}


def _project(p: Dict, x: jnp.ndarray):
    return (x @ p["w_z"], x @ p["w_x"], x @ p["w_B"], x @ p["w_C"],
            x @ p["w_dt"])


def ssd_chunked(xh: jnp.ndarray, a: jnp.ndarray, Bm: jnp.ndarray,
                Cm: jnp.ndarray,
                init_state: Optional[jnp.ndarray] = None,
                chunk: int = CHUNK
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.

    xh: (B,S,H,P) inputs premultiplied by dt; a: (B,S,H) log-decays (dt*A);
    Bm,Cm: (B,S,N).  Returns (y (B,S,H,P), final_state (B,H,P,N)).
    """
    Bb, S, H, P = xh.shape
    N = Bm.shape[-1]
    if S % chunk:
        chunk = S  # tiny sequences: one chunk
    nc = S // chunk

    xc = xh.reshape(Bb, nc, chunk, H, P)
    ac = a.reshape(Bb, nc, chunk, H).transpose(0, 3, 1, 2)   # (B,H,c,q)
    Bc = Bm.reshape(Bb, nc, chunk, N)
    Cc = Cm.reshape(Bb, nc, chunk, N)

    a_cum = jnp.cumsum(ac, axis=-1)                           # (B,H,c,q)
    L = jnp.exp(_segsum(ac))                                  # (B,H,c,q,q)

    # intra-chunk (diagonal blocks)
    y_diag = jnp.einsum("bcqn,bckn,bhcqk,bckhp->bcqhp", Cc, Bc, L, xc)

    # per-chunk final states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)           # (B,H,c,q)
    states = jnp.einsum("bckn,bhck,bckhp->bchpn", Bc, decay_states, xc)

    # inter-chunk recurrence (scan over chunks) - state carried in f32
    if init_state is None:
        init_state = jnp.zeros((Bb, H, P, N), jnp.float32)
    init_state = init_state.astype(jnp.float32)
    chunk_decay = jnp.exp(a_cum[..., -1])                     # (B,H,c)

    def step(s_prev, inp):
        st, dec = inp                                         # (B,H,P,N),(B,H)
        s_new = s_prev * dec[..., None, None] + st.astype(jnp.float32)
        return s_new, s_prev

    (final_state, states_in) = jax.lax.scan(
        step, init_state,
        (states.transpose(1, 0, 2, 3, 4),
         chunk_decay.transpose(2, 0, 1)))
    states_in = states_in.transpose(1, 0, 2, 3, 4)            # (B,c,H,P,N)

    # inter-chunk contribution
    state_decay = jnp.exp(a_cum)                              # (B,H,c,q)
    y_off = jnp.einsum("bcqn,bchpn,bhcq->bcqhp",
                       Cc.astype(jnp.float32), states_in, state_decay)

    y = (y_diag.astype(jnp.float32) + y_off).reshape(Bb, S, H, P)
    return y.astype(xh.dtype), final_state


def mamba2_forward(
    p: Dict, x: jnp.ndarray, *,
    d_inner: int, n_state: int, n_heads: int, head_dim: int,
    eps: float = 1e-5,
    ssm_state: Optional[jnp.ndarray] = None,
    conv_state: Optional[jnp.ndarray] = None,
    return_state: bool = False,
):
    """Full-sequence forward (train / prefill)."""
    B, S, _ = x.shape
    with jax.named_scope("mamba.proj"):
        z, xin, Bmat, Cmat, dt = _project(p, x)

    with jax.named_scope("mamba.conv"):
        xin, Bmat, Cmat, new_conv_state = _convs(p, xin, Bmat, Cmat,
                                                 conv_state or {})

    with jax.named_scope("mamba.ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
        A = -jnp.exp(p["A_log"])                                      # (H,)
        a = dt * A                                                # log decay
        xh = xin.reshape(B, S, n_heads, head_dim)
        xdt = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)

        y, final_state = ssd_chunked(xdt, a, Bmat.astype(x.dtype),
                                     Cmat.astype(x.dtype),
                                     init_state=ssm_state)
        y = y + xh * p["D"][None, None, :, None].astype(x.dtype)
        y = y.reshape(B, S, d_inner)

    with jax.named_scope("mamba.out"):
        # gated RMSNorm then output projection
        y = rms_norm(y * jax.nn.silu(z), p["norm_w"], eps)
        out = y @ p["out_proj"]
    if return_state:
        return out, (final_state, new_conv_state)
    return out


def mamba2_decode_step(
    p: Dict, x: jnp.ndarray, ssm_state: jnp.ndarray,
    conv_state: jnp.ndarray, *,
    d_inner: int, n_state: int, n_heads: int, head_dim: int,
    eps: float = 1e-5,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token recurrent update.  x: (B,1,D); state (B,H,P,N)."""
    B = x.shape[0]
    with jax.named_scope("mamba.proj"):
        z, xin, Bmat, Cmat, dt = _project(p, x)

    with jax.named_scope("mamba.conv"):
        xin, Bmat, Cmat, new_conv_state = _convs(p, xin, Bmat, Cmat,
                                                 conv_state)

    with jax.named_scope("mamba.state"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + p["dt_bias"])[:, 0]            # (B,H)
        A = -jnp.exp(p["A_log"])
        decay = jnp.exp(dt * A)                               # (B,H)
        xh = xin.reshape(B, n_heads, head_dim).astype(jnp.float32)
        Bv = Bmat[:, 0].astype(jnp.float32)                   # (B,N)
        Cv = Cmat[:, 0].astype(jnp.float32)

        # h' = decay * h + dt * (x outer B);  y = C . h' + D*x
        upd = (dt[..., None] * xh)[..., None] * Bv[:, None, None, :]
        new_state = (ssm_state * decay[..., None, None]
                     + upd.astype(ssm_state.dtype))
        y = jnp.einsum("bhpn,bn->bhp", new_state.astype(jnp.float32), Cv)
        y = y + xh * p["D"][None, :, None]
        y = y.reshape(B, 1, d_inner).astype(x.dtype)

    with jax.named_scope("mamba.out"):
        y = rms_norm(y * jax.nn.silu(z), p["norm_w"], eps)
        out = y @ p["out_proj"]
    return out, new_state, new_conv_state
