"""Sharding rules + multi-device integration (subprocess with 8 CPU devs).

The main pytest process keeps 1 device (per the assignment, the 512-device
flag is dry-run-only); multi-device behavior runs in subprocesses that set
XLA_FLAGS before importing jax.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import SHAPES
from repro.configs import ARCHS, get_config
from repro.models import transformer as T


class _FakeMesh:
    """Shape-only stand-in so spec generation needs no real devices."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def _rules(arch, multi_pod=False):
    from repro.parallel.sharding import ShardingRules
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16} if multi_pod
                     else {"data": 16, "model": 16})
    return ShardingRules(get_config(arch), mesh)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divisible(arch, multi_pod):
    """Every sharded dim divides its axis; no axis is used twice."""
    cfg = get_config(arch)
    rules = _rules(arch, multi_pod)
    params = T.param_shapes(cfg)
    specs = rules.param_specs(params)

    def check(path, leaf, spec):
        used = []
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= rules.mesh.shape[a]
                used.append(a)
            assert leaf.shape[i] % size == 0, (path, leaf.shape, spec)
        assert len(used) == len(set(used)), (path, spec)

    jax_tree_util = __import__("jax").tree_util
    jax_tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs)


@pytest.mark.parametrize("arch", ["internlm2-20b", "rwkv6-7b",
                                  "mixtral-8x7b", "zamba2-2.7b",
                                  "granite-4.0-h-small"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    rules = _rules(arch)
    shape = SHAPES["decode_32k"]
    caches = T.cache_shapes(cfg, shape.global_batch, shape.seq_len,
                            shape.seq_len // cfg.enc_seq_divisor
                            if cfg.is_encdec else 0)
    specs = rules.cache_specs(caches, shape.global_batch)

    def check(path, leaf, spec):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= rules.mesh.shape[a]
            assert leaf.shape[i] % size == 0, (path, leaf.shape, spec)

    __import__("jax").tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), caches, specs)

    # every K/V ring is lane-dense (L, B, T, kv*dh) with T, axis 2, on the
    # model axis; nothing else of it is split
    flat = jax.tree_util.tree_flatten_with_path(caches)[0]
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    rings = [(path, leaf, spec) for (path, leaf), spec
             in zip(flat, spec_leaves) if path[-1].key in ("k", "v")]
    assert rings or cfg.block_pattern[0] == "rwkv6"
    for path, leaf, spec in rings:
        assert leaf.shape[3] == cfg.n_kv_heads * cfg.head_dim, path
        assert spec[2] is not None and spec[3] is None, (path, spec)


_SUBPROCESS_TRAIN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.config import OptimizerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_sharded_train

    cfg = get_smoke_config("qwen3-0.6b")
    mesh = make_host_mesh(model=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    jstep, params, opt = build_sharded_train(cfg, mesh,
                                             OptimizerConfig(lr=1e-3))
    B, S = 8, 32
    key = jax.random.key(1)
    losses = []
    for i in range(4):
        batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
                 "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
        params, opt, metrics = jstep(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses   # same batch => loss must drop
    print(json.dumps({"losses": losses}))
""")

_SUBPROCESS_HIER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.parallel.collectives import hierarchical_grad_sync

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    grads = {"w": jnp.arange(8.0).reshape(4, 2), "b": jnp.ones(3)}
    with mesh:
        out = jax.jit(
            lambda g: hierarchical_grad_sync(g, mesh, compress=False))(grads)
    # psum over pod x data (4 copies of identical grads) => 4x
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(grads["w"]) * 4, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["b"]),
                               np.asarray(grads["b"]) * 4, rtol=1e-6)
    print(json.dumps({"ok": True}))
""")


def _run_sub(code: str) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1])
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_train_step_runs_and_learns():
    out = _run_sub(_SUBPROCESS_TRAIN)
    assert out["losses"][-1] < out["losses"][0]


def test_hierarchical_grad_sync_multipod():
    out = _run_sub(_SUBPROCESS_HIER)
    assert out["ok"]
