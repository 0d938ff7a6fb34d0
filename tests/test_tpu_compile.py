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
