"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ops import ssd
from repro.kernels.mamba2_ssd.ref import ssd_ref
from repro.kernels.moe_combine.ops import group_rows, moe_combine
from repro.kernels.moe_combine.ref import moe_combine_ref
from repro.kernels.moe_gmm.ops import ragged_matmul
from repro.kernels.moe_gmm.ref import ragged_ref
from repro.kernels.ring_decode.kernel import block_slots
from repro.kernels.ring_decode.ops import ring_decode
from repro.kernels.ring_decode.ref import ring_decode_ref
from repro.kernels.rwkv6_wkv.ops import wkv
from repro.kernels.rwkv6_wkv.ref import wkv_ref

KEY = jax.random.key(0)


@pytest.mark.parametrize("B,S,T,H,D", [
    (2, 512, 512, 4, 64),
    (1, 1024, 1024, 2, 128),
    (2, 256, 1024, 4, 64),
    (1, 512, 512, 3, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (False, 0)])
def test_flash_attention_sweep(B, S, T, H, D, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, H, D), dtype)
    v = jax.random.normal(ks[2], (B, T, H, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          impl="interpret")
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 4, 64, 64, 128),
    (1, 512, 2, 64, 32, 128),
    (2, 128, 8, 32, 64, 64),
])
def test_ssd_sweep(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 4)
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    a = -jnp.abs(jax.random.normal(ks[1], (B, S, H))) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    y1, s1 = ssd(xdt, a, Bm, Cm, chunk=chunk, impl="interpret")
    y2, s2 = ssd_ref(xdt, a, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("B,S,H,P,chunk", [
    (2, 64, 2, 32, 16),
    (1, 128, 4, 64, 16),
    (2, 32, 2, 16, 8),
])
def test_wkv_sweep(B, S, H, P, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, P))
    k = jax.random.normal(ks[1], (B, S, H, P))
    v = jax.random.normal(ks[2], (B, S, H, P))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, P)) * 0.5 - 2))
    u = jax.random.normal(ks[4], (H, P)) * 0.1
    y1, s1 = wkv(r, k, v, w, u, chunk=chunk, impl="interpret")
    y2, s2 = wkv_ref(r, k, v, w, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("sizes,K,N", [
    ([40, 0, 70, 90], 2048, 2048),     # an empty group, ragged edges
    ([13, 27, 0], 2048, 3072),         # rows under one row tile
], ids=["four_groups", "three_groups"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_gmm_sweep(sizes, K, N, dtype):
    """megablox's ``gmm`` (interpreted) against the oracle, with K and N
    wider than one of its 1024-wide tiles, so it accumulates over K tiles
    and writes several N tiles."""
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (sum(sizes), K), dtype)
    w = jax.random.normal(ks[1], (len(sizes), K, N), dtype) * 0.05
    gs = jnp.asarray(sizes, jnp.int32)
    got = ragged_matmul(x, w, gs, impl="interpret")
    want = ragged_ref(x, w, gs)
    assert got.shape == want.shape == (sum(sizes), N)
    assert got.dtype == dtype
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                               np.asarray(want, np.float32) / scale,
                               atol=tol)


def _routing(key, T, k, n_experts, layout):
    """(T, k) distinct experts a token.  ``fill_empty``: every token of the
    first 128-token tile picks expert 0, no token of the second does."""
    score = jax.random.uniform(key, (T, n_experts))
    if layout == "fill_empty":
        t = jnp.arange(T)[:, None]
        first = jnp.arange(n_experts)[None] == 0
        score = jnp.where(first & (t < 128), -1.0, score)
        score = jnp.where(first & (t >= 128) & (t < 256), 2.0, score)
    return jnp.argsort(score, axis=1)[:, :k].astype(jnp.int32)


@pytest.mark.parametrize("T,k,n_experts,held,D,layout", [
    (256, 8, 32, 32, 128, "uniform"),     # every expert held, k = 8
    (256, 10, 72, 9, 256, "uniform"),     # 9 of 72 held, k = 10
    (200, 8, 32, 32, 128, "uniform"),     # tokens past the last whole tile
    (300, 10, 72, 9, 128, "uniform"),     # both
    (384, 8, 32, 32, 128, "fill_empty"),  # an expert in every / no token
    (128, 2, 64, 1, 128, "uniform"),      # fewer held rows than a chunk
], ids=["all_held_k8", "share_k10", "ragged_tokens", "share_ragged",
        "fill_and_empty", "few_rows"])
def test_moe_combine_sweep(T, k, n_experts, held, D, layout):
    """The combine kernel (interpreted) against the oracle.  Rows past the
    held experts' (never computed) are NaN and must not reach the output;
    the sums differ from the oracle's only in their order, so by at most a
    bfloat16 rounding step."""
    ks = jax.random.split(KEY, 3)
    experts = _routing(ks[0], T, k, n_experts, layout)
    gates = jax.random.uniform(ks[1], (T, k), jnp.float32)
    counts, pos, bounds = group_rows(experts, held)
    n = int(counts.sum())
    ys = jax.random.normal(ks[2], (T * k, D), jnp.bfloat16)
    ys = ys.at[n:].set(jnp.nan)
    if layout == "fill_empty":
        per_tile = np.diff(np.asarray(bounds)[:, 0])
        assert per_tile[0] == 128 and per_tile[1] == 0
    got = moe_combine(ys, pos, gates, bounds, impl="interpret")
    want = moe_combine_ref(ys, pos, gates)
    assert got.shape == (T, D) and got.dtype == jnp.bfloat16
    assert not np.isnan(np.asarray(want, np.float32)).any()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -10)


@pytest.mark.parametrize("N,B,T,C,R,n", [
    (3, 2, 40, 64, 8, 1),       # T in one tile, not a multiple of 128
    (2, 2, 1168, 512, 16, 1),   # granite's ring and lanes at 2 lanes
    (2, 1, 4096, 1024, 16, 2),  # four tiles, two new tokens
    (2, 1, 300, 8192, 8, 1),    # 128-slot tiles, the last past the ring
    (2, 2, 384, 256, 8, 0),     # a read-only cache (cross-attention)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_decode_sweep(N, B, T, C, R, n, dtype):
    """The decode kernel (interpreted) against the oracle: ring 1 of a
    stack read by index, masked slots, and new tokens standing in for
    their slots, among them the tile's last slot."""
    ks = jax.random.split(KEY, 6)
    q = jax.random.normal(ks[0], (B, R, C), dtype)
    rk = jax.random.normal(ks[1], (N, B, T, C), dtype)
    rv = jax.random.normal(ks[2], (N, B, T, C), dtype)
    valid = jax.random.uniform(ks[3], (R, T)) > 0.3
    new = dict(new_k=jax.random.normal(ks[4], (B, n, C), dtype),
               new_v=jax.random.normal(ks[5], (B, n, C), dtype),
               slots=jnp.asarray([5, T - 1][:n], jnp.int32)) if n else {}
    args = (q, rk, rv, jnp.int32(1), valid)
    got = ring_decode(*args, **new, scale=0.125, impl="interpret")
    want = ring_decode_ref(*args, **new, scale=0.125)
    assert got.shape == (B, R, C) and got.dtype == dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_ring_decode_tiles():
    """A ring is read whole where a K or V tile of it fits 2.5 MiB, else in
    tiles of whole 128-slot lane tiles that fit."""
    assert block_slots(1168, 512, 2) == 1168       # granite's
    assert block_slots(1168, 1024, 2) == 1168      # qwen3's
    assert block_slots(1168, 2560, 2) == 512       # zamba2's
    assert block_slots(1168, 4096, 2) == 256       # deepseek's
    assert block_slots(32768, 8192, 2) == 128


def test_flash_matches_model_xla_path():
    """The in-model XLA flash (custom_vjp) and the Pallas kernel agree."""
    from repro.models.layers import flash_attention as xla_flash

    ks = jax.random.split(KEY, 3)
    B, S, H, D = 2, 512, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    got = flash_attention(q, k, v, causal=True, impl="interpret")
    want = xla_flash(q, k, v, jnp.arange(S), jnp.arange(S), 0, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("op,args", [
    (flash_attention, lambda: [jnp.zeros((1, 128, 1, 128))] * 3),
    (ssd, lambda: [jnp.zeros((1, 128, 1, 64)), jnp.zeros((1, 128, 1)),
                   jnp.zeros((1, 128, 64)), jnp.zeros((1, 128, 64))]),
    (wkv, lambda: [jnp.zeros((1, 16, 1, 64))] * 4 + [jnp.zeros((1, 64))]),
])
def test_auto_impl_refuses_non_tpu_backend(op, args):
    """impl="auto" is Pallas on a TPU; elsewhere it raises rather than
    quietly running the interpreter."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="TPU only"):
        op(*args())
