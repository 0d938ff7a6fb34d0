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


# _dropless's temporaries at each cell's prefill, compiled for a described
# v5e with the unsort scatter, the (R, D) gather and its float32 copy of the
# combine it had before the combine kernel
PARENT_TEMP = {"granite-moe-1b-a400m": 1611257856,
               "granite-4.0-h-small": 5637692928}


@pytest.mark.parametrize("arch,slots,fall", [
    ("granite-moe-1b-a400m", 32, 500 << 20),
    ("granite-4.0-h-small", 16, 3 << 30),
])
def test_dropless_prefill_combine_copies_nothing_for_v5e(arch, slots, fall,
                                                         one_chip,
                                                         monkeypatch):
    """The dropless MoE at a cell's prefill (``slots`` lanes of 1024
    tokens) on a v5e: no scatter under ``moe.route`` or ``moe.combine``
    (group sizes and sorted positions come from a cumulative sum), no (R, D)
    result under ``moe.combine`` (the kernel reads the expert rows where the
    grouped matmul wrote them), and temporaries ``fall`` bytes or more
    below the old combine's.  The peak is now the grouped matmuls': the
    sorted rows and the gate and up projections' outputs, live together."""
    import functools
    import re

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.moe_combine import ops as combine
    from repro.kernels.moe_gmm import ops as gmm
    from repro.models import moe

    monkeypatch.setattr(moe, "ragged_matmul",
                        functools.partial(gmm.ragged_matmul, impl="pallas"))
    monkeypatch.setattr(moe, "moe_combine",
                        functools.partial(combine.moe_combine, impl="pallas"))
    cfg = get_config(arch)
    B, S, D, F = slots, 1024, cfg.d_model, cfg.moe_d_ff
    E, k, held = cfg.n_experts, cfg.n_experts_active, cfg.n_experts_held
    R = B * S * k
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    bank = {"wi_gate": spec((held, D, F), jnp.bfloat16),
            "wi_up": spec((held, D, F), jnp.bfloat16),
            "wo": spec((held, F, D), jnp.bfloat16)}
    compiled = jax.jit(lambda p, x, g, e: moe._dropless(p, x, g, e, E)).lower(
        bank, spec((B, S, D), jnp.bfloat16), spec((B, S, k), jnp.float32),
        spec((B, S, k), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    wide = re.compile(rf"= (f32|bf16)\[{R},{D}\]")
    for line in text.splitlines():
        scope = re.search(r'op_name="[^"]*(moe\.route|moe\.combine)/', line)
        if not scope:
            continue
        op = line.split("metadata=")[0]
        assert not re.search(r"\bscatter(-add)?\(", op), line
        if scope.group(1) == "moe.combine":
            assert not wide.search(op), line
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= PARENT_TEMP[arch] - fall
    assert temp < (R * D + 2 * R * F) * 2 + (16 << 20)


def test_dropless_train_step_differentiates_the_combine_kernel_for_v5e(
        one_chip, monkeypatch):
    """A training step's gradient through the dropless MoE at granite-moe's
    widths compiles for a v5e with the combine kernel in its forward: the
    kernel's gradients are XLA ops (a Pallas call with scalar prefetch has
    no derivative of its own)."""
    import functools

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.moe_combine import ops as combine
    from repro.kernels.moe_gmm import ops as gmm
    from repro.models import moe

    monkeypatch.setattr(moe, "ragged_matmul",
                        functools.partial(gmm.ragged_matmul, impl="pallas"))
    monkeypatch.setattr(moe, "moe_combine",
                        functools.partial(combine.moe_combine, impl="pallas"))
    cfg = get_config("granite-moe-1b-a400m")
    B, S, D, F = 2, 512, cfg.d_model, cfg.moe_d_ff
    E, k = cfg.n_experts, cfg.n_experts_active
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    p = {"router": spec((D, E), jnp.float32),
         "wi_gate": spec((E, D, F), jnp.bfloat16),
         "wi_up": spec((E, D, F), jnp.bfloat16),
         "wo": spec((E, F, D), jnp.bfloat16)}

    def loss(p, x):
        out, _ = moe.moe_mlp(p, x, n_experts=E, top_k=k)
        return jnp.sum(jnp.square(out.astype(jnp.float32)))  # needs out

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, spec((B, S, D), jnp.bfloat16)).compile()
    assert "moe_combine_fwd" in compiled.as_text()
