"""Each Pallas kernel compiled at real widths for a TPU v5e that is described,
not attached.

The TPU compiler refuses what interpret mode accepts: blocks that break the
(8, 128) tiling, primitives Mosaic cannot lower, and more than the scoped
VMEM a kernel may use.  Nothing runs here, so these tests say nothing about
results or times.  The topology is described inside a fixture: only one
process at a time may load the TPU library, and every test worker imports
this module.
"""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cases import real_width_cases

CASES = {c.name: c for c in real_width_cases()}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    compiled = jax.jit(lambda *a: case.op(*a, impl="pallas")).lower(
        *case.abstract(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0


@pytest.mark.parametrize("arch,slots", [("qwen3-0.6b", 16),
                                        ("granite-moe-1b-a400m", 32)])
def test_decode_cache_is_lane_dense_for_v5e(arch, slots, one_chip,
                                            monkeypatch):
    """``JaxServeEngine``'s decode program at full width, at the benchmark
    cells' slots and ring (1024-token prompts, 128 tokens out), reads and
    writes its K/V ring in place with no lane padding: the aliased bytes
    are the buffers' logical bytes and ``pos``'s one 512-byte tile.  A head
    dim of 64 (granite) in the minor place would be laid out T-minor and
    padded to the lanes; a ring the TPU lays out otherwise than the decode
    kernel reads it would be copied whole.  Its temporaries hold less than
    one layer's K ring: no layer's ring is copied out of the stack."""
    import functools

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.moe_gmm import ops as gmm
    from repro.kernels.ring_decode import ops as ring
    from repro.models import init_params, layers, moe
    from repro.models.transformer import RING_ALIGN
    from repro.serving.engine import JaxServeEngine

    # the TPU's path: the backend seen while tracing here is the CPU
    monkeypatch.setattr(moe, "ragged_matmul",
                        functools.partial(gmm.ragged_matmul, impl="pallas"))
    monkeypatch.setattr(layers, "ring_decode",
                        functools.partial(ring.ring_decode, impl="pallas"))
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    max_len = 1024 + 128 + 4
    eng = JaxServeEngine(cfg, params, n_slots=slots, max_len=max_len)
    batch = {"tokens": jax.ShapeDtypeStruct((slots, 1024), jnp.int32)}
    tok, cache = jax.eval_shape(eng.prefill_fn, params, batch)[:2]
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    compiled = eng.decode_fn.lower(on_chip(params), on_chip(cache),
                                   on_chip(tok)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    nbytes = lambda a: a.size * a.dtype.itemsize
    k, v = cache["layers"]["k"], cache["layers"]["v"]
    ring = -(-max_len // RING_ALIGN) * RING_ALIGN
    assert k.shape == (cfg.n_layers, slots, ring,
                       cfg.n_kv_heads * cfg.head_dim)
    assert 0 <= mem.alias_size_in_bytes - nbytes(k) - nbytes(v) <= 512
    assert mem.temp_size_in_bytes < nbytes(k) // cfg.n_layers


def test_hybrid_decode_takes_its_cache_in_place_for_v5e(one_chip,
                                                        monkeypatch):
    """granite-4.0-h-small's decode program at full width, at the cell's 16
    slots and ring: the whole hybrid cache (each run's Mamba2 SSM and conv
    states, the attention layer's K/V ring, ``pos``) is aliased to the
    output, within ``pos``'s one 512-byte tile, and the temporaries hold
    less than one layer's SSM state: each state is rewritten in place, and
    no state or ring is copied out of its stack."""
    import functools

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.moe_gmm import ops as gmm
    from repro.kernels.ring_decode import ops as ring
    from repro.models import init_params, layers, moe
    from repro.serving.engine import JaxServeEngine

    monkeypatch.setattr(moe, "ragged_matmul",
                        functools.partial(gmm.ragged_matmul, impl="pallas"))
    monkeypatch.setattr(layers, "ring_decode",
                        functools.partial(ring.ring_decode, impl="pallas"))
    cfg, slots = get_config("granite-4.0-h-small"), 16
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    eng = JaxServeEngine(cfg, params, n_slots=slots, max_len=1024 + 128 + 4)
    batch = {"tokens": jax.ShapeDtypeStruct((slots, 1024), jnp.int32)}
    tok, cache = jax.eval_shape(eng.prefill_fn, params, batch)[:2]
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    mem = eng.decode_fn.lower(on_chip(params), on_chip(cache),
                              on_chip(tok)).compile().memory_analysis()
    nbytes = lambda a: a.size * a.dtype.itemsize
    assert [("ssm" in r) for r in cache["layers"]] == [True, False, True]
    state = cache["layers"][0]["ssm"]
    assert state.shape == (5, slots, 128, 64, 128)
    total = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert 0 <= mem.alias_size_in_bytes - total <= 512
    assert mem.temp_size_in_bytes < nbytes(state) // state.shape[0]
