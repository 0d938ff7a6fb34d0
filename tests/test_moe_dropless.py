"""The dropless MoE and Granite's multipliers against plain references, on
the CPU at the smoke size with seeded random weights.

* ``moe_mlp`` against every expert run densely and summed with the
  renormalised top-k gates, under uniform and under skewed routing, at 8
  experts top-2 and at granite's 32 experts top-8;
* ``ragged_matmul``'s two implementations against each other, empty groups
  and rows padded to the kernel's tile included;
* the granite smoke model, prefill and then 8 decode steps through the
  cache, against the benchmark's plain reference (``bench/configs/
  decoder.py``) on the same weights; the comparison fails with any one
  multiplier left out or with one expert a token fewer than the reference
  routes to;
* the serving engine's MoE counters after a ``generate`` with parked
  streams.
"""

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.moe_combine.kernel import TILE
from repro.kernels.moe_combine.ops import group_rows
from repro.kernels.moe_gmm.ops import ragged_matmul, row_tile
from repro.models import decode_step, prefill
from repro.models.moe import moe_mlp, moe_params
from repro.serving.engine import JaxServeEngine

ROOT = Path(__file__).resolve().parents[1]
ARCH = "granite-moe-1b-a400m"
E, K, D, F = 8, 2, 32, 48


def _dense_moe(p, x, top_k):
    """Every expert on every token, weighted by the renormalised top-k
    gates (zero for the rest): the MoE with nothing dropped, in float32."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    gates = (jax.nn.one_hot(idx, probs.shape[-1])
             * (top / top.sum(-1, keepdims=True))[..., None]).sum(-2)
    h = jax.nn.silu(jnp.einsum("bsd,edf->ebsf", x, p["wi_gate"])) \
        * jnp.einsum("bsd,edf->ebsf", x, p["wi_up"])
    outs = jnp.einsum("ebsf,efd->ebsd", h, p["wo"])
    return jnp.einsum("ebsd,bse->bsd", outs, gates)


def _moe_inputs(skewed, seed=0, B=2, S=64, n_experts=E):
    kp, kx = jax.random.split(jax.random.key(seed))
    p = moe_params(kp, D, F, n_experts, jnp.float32)
    x = jax.random.normal(kx, (B, S, D))
    if skewed:
        # a shared direction in every token that the router maps to expert
        # 0: it takes nearly every token's first slot
        base = jnp.ones((D,)) / jnp.sqrt(D)
        x = x + 6.0 * base
        p["router"] = p["router"].at[:, 0].add(4.0 * base)
    return p, x


# (experts, top-k): a small router, and granite-moe-1b-a400m's published one
ROUTINGS = pytest.mark.parametrize("n_experts,top_k", [(E, K), (32, 8)],
                                   ids=["8x2", "granite_32x8"])


@ROUTINGS
@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skewed"])
def test_dropless_equals_dense_gated_sum(skewed, n_experts, top_k):
    p, x = _moe_inputs(skewed, n_experts=n_experts)
    want = _dense_moe(p, x, top_k)
    got, _ = moe_mlp(p, x, n_experts=n_experts, top_k=top_k)
    # float32 both ways: only the order of the sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@ROUTINGS
@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skewed"])
def test_dropless_counts_every_row(skewed, n_experts, top_k):
    p, x = _moe_inputs(skewed, seed=3, n_experts=n_experts)
    B, S, _ = x.shape
    _, aux = moe_mlp(p, x, n_experts=n_experts, top_k=top_k)
    assert int(aux["moe_rows"]) == B * S * top_k
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    idx = np.asarray(jax.lax.top_k(probs, top_k)[1]).ravel()
    assert int(aux["moe_max_group"]) == np.bincount(
        idx, minlength=n_experts).max()
    if skewed:
        assert int(aux["moe_max_group"]) >= B * S * 0.9


def test_dropless_rows_are_the_groups_the_matmuls_take(monkeypatch):
    """``moe_rows`` sums the group sizes the grouped matmuls are given, so
    a (token, expert) row that lands in no group (here one routed to an
    expert id past the last) is missing from the count and the output."""
    p, x = _moe_inputs(False, seed=5)
    B, S, _ = x.shape
    want = _dense_moe(p, x, K)
    top_k = jax.lax.top_k

    def one_row_astray(a, k):
        vals, idx = top_k(a, k)
        return vals, idx.at[0, 0, 0].set(E)

    monkeypatch.setattr(jax.lax, "top_k", one_row_astray)
    got, aux = moe_mlp(p, x, n_experts=E, top_k=K)
    assert int(aux["moe_rows"]) == B * S * K - 1
    # the astray row's gated output is gone from its token alone
    assert float(jnp.abs(got[0, 0] - want[0, 0]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(got)[:, 1:], np.asarray(want)[:, 1:],
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("sizes", [
    [40, 0, 70, 10],          # an empty group inside
    [0, 0, 120, 0],           # one group holds every row
    [13, 29, 31, 47],         # none on a tile boundary
    [0, 5, 0, 0],             # rows past the kernel's tile: padded
], ids=["empty_inside", "one_group", "ragged", "padded"])
def test_ragged_dot_matches_megablox(sizes):
    """The CPU path (jax.lax.ragged_dot) and the TPU path (megablox's gmm,
    interpreted) agree; every row lies in a group."""
    M = sum(sizes)
    kx, kw = jax.random.split(jax.random.key(M))
    x = jax.random.normal(kx, (M, 128), jnp.float32)
    w = jax.random.normal(kw, (len(sizes), 128, 256), jnp.float32) * 0.1
    gs = jnp.asarray(sizes, jnp.int32)
    ref = ragged_matmul(x, w, gs, impl="ref")
    got = ragged_matmul(x, w, gs, impl="interpret")
    assert got.shape == ref.shape == (M, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # each row against its group's weights, as a plain matmul
    grp = np.repeat(np.arange(len(sizes)), sizes)
    want = np.einsum("md,mdn->mn", np.asarray(x), np.asarray(w)[grp])
    np.testing.assert_allclose(np.asarray(ref), want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,k,n_experts,held,astray", [
    (127, 2, 8, 8, False),        # every expert held, a row short of a tile
    (300, 8, 32, 32, False),      # tokens past the last whole tile
    (256, 10, 72, 9, False),      # a share of the experts, k = 10
    (129, 2, 8, 8, True),         # ids past the last expert, a row past
], ids=["8x2", "granite_32x8_ragged", "share_72x10", "astray"])
@pytest.mark.parametrize("seed", [0, 1])
def test_group_rows_equal_bincount_and_sort_inverse(T, k, n_experts, held,
                                                    astray, seed):
    """``group_rows``'s scatter-free group sizes, positions and slab table
    on random routings: the sizes are ``bincount``'s over the held experts,
    each row's position is where the stable sort by expert puts it (the
    inverse of ``argsort``), -1 for rows in no group, and the table holds
    the first row of each group in each of the kernel's token tiles."""
    ks = jax.random.split(jax.random.key(seed), 2)
    experts = jnp.argsort(jax.random.uniform(ks[0], (T, n_experts)),
                          axis=1)[:, :k].astype(jnp.int32)
    if astray:
        experts = jnp.where(jax.random.uniform(ks[1], (T, k)) < 0.1,
                            n_experts + 3, experts)
    counts, pos, bounds = group_rows(experts, held)
    flat = np.asarray(experts).ravel()
    inside = flat < held
    want = np.bincount(flat[inside], minlength=held)
    np.testing.assert_array_equal(np.asarray(counts), want)
    order = np.argsort(flat, kind="stable")
    inverse = np.empty_like(order)
    inverse[order] = np.arange(flat.size)
    got = np.asarray(pos).ravel()
    np.testing.assert_array_equal(got[inside], inverse[inside])
    assert (got[~inside] == -1).all()
    starts = np.concatenate([[0], np.cumsum(want)[:-1]])
    tok = np.repeat(np.arange(T), k)
    table = np.asarray(bounds)
    assert table.shape == (-(-T // TILE) + 1, held)
    for i in range(table.shape[0]):
        before = np.bincount(flat[inside & (tok < i * TILE)],
                             minlength=held)
        np.testing.assert_array_equal(table[i], starts + before)


@pytest.mark.parametrize("held,first,S", [
    (8, 0, 64),         # every expert held, whole token tiles
    (3, 2, 50),         # experts 2..4 of 8 held, tokens past a tile
], ids=["all_held", "share_ragged"])
def test_dropless_grad_through_combine_kernel_equals_oracle(held, first, S,
                                                            monkeypatch):
    """A training step differentiates through the combine kernel: the
    gradients of ``moe_mlp`` (router, experts, input) with the kernel
    (interpreted) equal the oracle's, in bfloat16 as a TPU runs it."""
    import functools

    from repro.kernels.moe_combine import ops as combine
    from repro.models import moe

    p, x = _moe_inputs(False, seed=11, S=S)
    p = {n: v if n == "router" else v[first:first + held].astype(
        jnp.bfloat16) for n, v in p.items()}
    x = x.astype(jnp.bfloat16)
    ct = jax.random.normal(jax.random.key(12), x.shape, jnp.bfloat16)

    def grads(impl):
        monkeypatch.setattr(moe, "moe_combine",
                            functools.partial(combine.moe_combine, impl=impl))

        def loss(p, x):
            out, _ = moe_mlp(p, x, n_experts=E, top_k=K, first_expert=first)
            return jnp.sum(out.astype(jnp.float32) * ct)

        return jax.grad(loss, argnums=(0, 1))(p, x)

    got, want = grads("interpret"), grads("ref")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        w = np.asarray(w, np.float32)
        assert np.abs(w).max() > 0
        np.testing.assert_allclose(np.asarray(g, np.float32), w,
                                   rtol=2 ** -7,
                                   atol=2 ** -7 * np.abs(w).max())


def test_row_tile():
    assert row_tile(32 * 8, 32) == 16           # decode: 8 rows a group
    assert row_tile(32 * 1024 * 8, 32) == 512   # prefill
    assert row_tile(200, 4) == 64


# ---------------------------------------------------------------------------
# the granite smoke model against the benchmark's plain reference
# ---------------------------------------------------------------------------

def _decoder():
    spec = importlib.util.spec_from_file_location(
        "bench_decoder", ROOT / "bench" / "configs" / "decoder.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _granite(attention_multiplier=0.015625):
    """The program's smoke configuration with the published attention
    multiplier (at the smoke head width of 16, the smoke value 0.25 is
    1/sqrt(16), which the program would compute without it), and the same
    in the benchmark file's keys."""
    cfg = dataclasses.replace(get_smoke_config(ARCH),
                              attention_multiplier=attention_multiplier)
    conf = json.loads((ROOT / "bench" / "configs"
                       / "granite-3.0-1b-a400m-base.json").read_text())
    c = dict(conf["config"], num_hidden_layers=cfg.n_layers,
             hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
             num_key_value_heads=cfg.n_kv_heads,
             intermediate_size=cfg.moe_d_ff, vocab_size=cfg.vocab_size,
             num_local_experts=cfg.n_experts,
             num_experts_per_tok=cfg.n_experts_active,
             attention_multiplier=attention_multiplier)
    assert _decoder().program_expect(c) == {
        k: getattr(cfg, k) for k in _decoder().program_expect(c)}
    return cfg, c


P, G = 32, 8     # prompt, then 8 decode steps through the cache
# Widest |program - reference| logit over the reference's largest.  float32
# program: only the order of the sums differs (observed 3.4e-7); bfloat16
# program, as served: matmul operands and the residual stream rounded to 8
# bits of mantissa (observed 0.6-0.8%).  Faults move it by 49% (attention
# multiplier), 45% (one expert a token short), 112-500% (the other
# multipliers).
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


def _program_logits(cfg, w, toks):
    """Logits at positions P-1 .. P+G-1: the prefill's last, then each
    decode step's, over the published vocabulary."""
    l, cache = prefill(cfg, w, {"tokens": toks[:, :P]}, max_len=P + G + 4)
    out = [l[:, 0]]
    for t in range(G):
        l, cache = decode_step(cfg, w, cache, toks[:, P + t][:, None])
        out.append(l[:, 0])
    return np.stack([np.asarray(o, np.float32) for o in out], 1)[
        ..., :cfg.vocab_size]


def _gap(cfg, c, dtype, seed=0):
    dec = _decoder()
    w = dec.make_weights(c, jax.random.key(seed))
    if dtype == "float32":
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    cfg = dataclasses.replace(cfg, dtype=dtype)
    toks = jax.random.randint(jax.random.key(seed + 100), (2, P + G), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(dec.reference_logits(
            c, w, toks[:, :P + G], G + 1))
    got = _program_logits(cfg, w, toks)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [0, 7])
def test_granite_prefill_and_decode_match_reference(dtype, seed):
    cfg, c = _granite()
    assert _gap(cfg, c, dtype, seed) < TOL[dtype]


@pytest.mark.parametrize("fault", [
    dict(embedding_multiplier=None), dict(attention_multiplier=None),
    dict(residual_multiplier=None), dict(logits_scaling=None),
    # top-1 routing where the smoke config and the reference route to 2
    dict(n_experts_active=1),
], ids=["no_embedding_multiplier", "no_attention_multiplier",
        "no_residual_multiplier", "no_logits_scaling", "top_k_one_short"])
def test_granite_reference_check_catches(fault):
    cfg, c = _granite()
    assert _gap(dataclasses.replace(cfg, **fault), c, "float32") \
        > 100 * TOL["float32"]


def test_engine_counts_every_moe_row():
    """5 streams on 2 slots: waves of 2, 2 and 1 lanes, each a prefill of
    the prompt and ``gen_len`` decode steps; every (token, expert) row of
    every layer is computed."""
    cfg = get_smoke_config(ARCH)
    from repro.models import init_params

    eng = JaxServeEngine(cfg, init_params(cfg, jax.random.key(0)),
                         n_slots=2, max_len=24)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (5, 12)).astype(np.int32)
    toks = eng.generate(prompts, gen_len=4)
    assert toks.shape == (5, 4)
    assert eng.stats.waves == 3 and eng.stats.streams_parked == 3
    k, L = cfg.n_experts_active, cfg.n_layers
    assert eng.moe_stats.rows == 5 * (12 + 4) * k * L
    # the largest group of one layer in one step: at least the mean, at
    # most every row of the largest prefill (2 lanes x 12 tokens)
    assert 2 * 12 * k / cfg.n_experts <= eng.moe_stats.max_group <= 2 * 12


def test_dense_engine_has_no_moe_counts():
    cfg = get_smoke_config("qwen3-0.6b")
    from repro.models import init_params

    eng = JaxServeEngine(cfg, init_params(cfg, jax.random.key(0)),
                         n_slots=2, max_len=24)
    out = eng.prefill_fn(eng.params, {"tokens": jnp.zeros((2, 8), jnp.int32)})
    assert len(out) == 2
    eng.generate(np.zeros((3, 8), np.int32), gen_len=2)
    assert (eng.moe_stats.rows, eng.moe_stats.max_group) == (0, 0)
