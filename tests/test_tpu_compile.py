"""Compile the Pallas kernels of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described, not attached.  Each test compiles
one kernel at real widths with ``interpret=False`` and checks that the
program holds the Mosaic kernel (``tpu_custom_call``).  What the chip's
compiler refuses — a block shape off the (8, 128) tiling, more VMEM than
a kernel may use — fails here, where interpret-mode tests cannot see it.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.sedov_stencil import sedov_step_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip would be written to a persistent
    # cache that cannot read it back: keep the cache off for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *structs) -> str:
    return jax.jit(fn).lower(*structs).compile().as_text()


@pytest.mark.parametrize("K,G", [(32, 1), (8, 4)], ids=["mha", "gqa"])
def test_paged_attention_compiles_at_real_widths(one_chip, K, G):
    """deepseek-7b decode widths: dh 128, page_size 16, 8 slots x 32
    pages; MHA (32 kv heads) and a GQA ratio of 4."""
    slots, dh, page_size, max_pages = 8, 128, 16, 32
    num_pages = slots * max_pages + 1

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = S((num_pages, page_size, K, dh), jnp.bfloat16)
    text = _compile_text(
        lambda q, k, v, pt, kl: paged_attention_pallas(
            q, k, v, pt, kl, interpret=False),
        S((slots, K * G, dh), jnp.bfloat16), pool, pool,
        S((slots, max_pages), jnp.int32), S((slots,), jnp.int32))
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles_at_real_widths(one_chip):
    x = jax.ShapeDtypeStruct((512, 4096), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.bfloat16, sharding=one_chip)
    text = _compile_text(
        lambda x, w: rmsnorm_pallas(x, w, interpret=False), x, w)
    assert "tpu_custom_call" in text


def test_sedov_step_compiles_on_a_64_cube(one_chip):
    n = 64

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    state = {"rho": S((n, n, n)), "e": S((n, n, n)), "v": S((3, n, n, n)),
             "t": S(())}
    text = _compile_text(
        lambda st, dt: sedov_step_pallas(st, dt, interpret=False),
        state, S(()))
    assert "tpu_custom_call" in text
