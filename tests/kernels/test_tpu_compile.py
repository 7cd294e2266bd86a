"""The main path's kernels compile for a TPU v5e, at the widths the
Epinions-profile graph (75,879 nodes) gives them.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e.  Interpret-mode tests (``test_kernels.py``) check the kernels' logic
but not what Mosaic accepts — block shapes against the (8, 128) tiling,
DMA slice alignment, supported casts, scoped memory.  These compiles do.
The topology is described inside a module fixture (never at import
time), and JAX's persistent compilation cache is off around the compiles:
an executable for a described device cannot be read back.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.slabgeom import pow2_at_least
from repro.jaxgm.device_graph import padded_size
from repro.kernels.bitmm import bitmm_pallas
from repro.kernels.gather_intersect import (expand_pairs,
                                            gather_intersect_pallas)
from repro.kernels.intersect import intersect_pallas

EPINIONS_NODES = 75_879
# resident enumerator: pages of up to 8192 frontier rows (the mjoin slab
# cap), padded to a power of two
LARGEST_PAGE = pow2_at_least(8192)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("threshold", [True, False])
def test_bitmm_compiles_at_epinions_width(one_chip, threshold):
    n_pad = padded_size(EPINIONS_NODES, 512)
    a = _spec(one_chip, (n_pad, n_pad // 32), jnp.uint32)
    x = _spec(one_chip, (n_pad, 8), jnp.float32)          # max_q = 8
    _assert_kernel(bitmm_pallas.lower(a, x, threshold=threshold).compile())


@pytest.mark.parametrize("f,k,w", [(128, 1, 128), (LARGEST_PAGE, 4, 1024),
                                   (1024, 3, 2432)])
def test_gather_intersect_compiles_at_resident_widths(one_chip, f, k, w):
    matrix = _spec(one_chip, (100_000, w), jnp.uint32)     # rows % 8 == 0
    idx = _spec(one_chip, (f, k), jnp.int32)
    _assert_kernel(gather_intersect_pallas.lower(
        matrix, idx, w32=w - 64, bf=8).compile())


def test_intersect_compiles(one_chip):
    rows = _spec(one_chip, (128, 4, 256), jnp.uint32)
    _assert_kernel(intersect_pallas.lower(rows, bf=128, bw=256).compile())


def test_expand_pairs_compiles_at_largest_page(one_chip):
    acc = _spec(one_chip, (LARGEST_PAGE, 1024), jnp.uint32)
    compiled = expand_pairs.lower(acc, n_i=30_000, size=1 << 16).compile()
    assert compiled.memory_analysis() is not None
