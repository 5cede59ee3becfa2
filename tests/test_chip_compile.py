"""Ahead-of-time compiles of the Pallas TTL kernel for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached.  It refuses what the chip would refuse and the
interpreter lets through: slices not aligned to the tiling, more fast memory
than a kernel may use.  These tests compile the refresh loop's kernel at the
shapes the store runs -- a 3- and a 9-region refresh, and the §6.7.3 batch
of 1024 edge problems -- and check that the kernel is really in the program;
and they compile a whole refresh, inputs to decision, as the one program the
refresh loop runs, and the largest program of a maintenance tick's sweep.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ops import SWEEP_MAX_EDGES, ttl_refresh_decision
from repro.kernels.ttl_scan import ttl_cost_surface


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("e_dim,c_dim", [(2, 800), (8, 800), (1024, 800)])
def test_ttl_cost_surface_compiles_for_v5e(one_chip, no_persistent_cache,
                                           e_dim, c_dim):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    rows, per_edge = f32(e_dim, c_dim), f32(e_dim)
    compiled = ttl_cost_surface.lower(
        rows, rows, rows, f32(c_dim), per_edge, per_edge, per_edge).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e_dim,c_dim", [(2, 800), (8, 800)])
def test_ttl_refresh_is_one_program_for_v5e(one_chip, no_persistent_cache,
                                           e_dim, c_dim):
    """A refresh's packed inputs, the Pallas surface, the TTL=0 column and
    each row's reduction compile to one program with the kernel inside it,
    returning the ``[3, E]`` decision."""
    packed = jax.ShapeDtypeStruct((3 * c_dim + 3 * e_dim + 1,), jnp.float32,
                                  sharding=one_chip)
    edges = jax.ShapeDtypeStruct((c_dim,), jnp.float32, sharding=one_chip)
    compiled = ttl_refresh_decision.lower(packed, edges, n_rows=1).compile()
    text = compiled.as_text()
    assert text.count("HloModule ") == 1
    assert "tpu_custom_call" in text
    assert compiled.out_info.shape == (3, e_dim)


def test_largest_sweep_program_compiles_for_v5e(one_chip, no_persistent_cache):
    """A 9-region sweep's largest program: ``SWEEP_MAX_EDGES`` edge rows,
    each pair's histogram row serving its 8 incoming edges, in one program
    with the kernel inside it and within the chip's scoped VMEM, returning
    the ``[3, E]`` decision."""
    c_dim, e_dim = 800, SWEEP_MAX_EDGES
    n_rows = e_dim // 8
    packed = jax.ShapeDtypeStruct((n_rows * (3 * c_dim + 1) + 3 * e_dim,),
                                  jnp.float32, sharding=one_chip)
    edges = jax.ShapeDtypeStruct((c_dim,), jnp.float32, sharding=one_chip)
    compiled = ttl_refresh_decision.lower(packed, edges,
                                          n_rows=n_rows).compile()
    text = compiled.as_text()
    assert text.count("HloModule ") == 1
    assert "tpu_custom_call" in text
    assert compiled.out_info.shape == (3, e_dim)
