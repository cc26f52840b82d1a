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

import math

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


def test_paged_attention_compiles_at_longctx_widths(one_chip):
    """stablelm-2-1.6b's longctx cell: 6 slots x 256 pages of 16, 32 kv
    heads of 64 packed to (16, 128) rows, the 24-layer pool read at a
    traced layer, as the decode step's layer scan calls it."""
    from repro.serving.pool import page_rows
    slots, K, dh, page_size, max_pages, layers = 6, 32, 64, 16, 256, 24
    rows, lanes = page_rows(K, dh)
    assert (rows, lanes) == (16, 128)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = S((layers, slots * max_pages + 1, page_size, rows, lanes),
             jnp.bfloat16)
    text = _compile_text(
        lambda q, k, v, pt, kl, ly: paged_attention_pallas(
            q, k, v, pt, kl, ly, interpret=False),
        S((slots, K, dh), jnp.bfloat16), pool, pool,
        S((slots, max_pages), jnp.int32), S((slots,), jnp.int32),
        S((), jnp.int32))
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


# ---------------------------------------------------------------------------
# The engine's paged steps: the KV pool stays in place


def _paged_step(one_chip, monkeypatch, step):
    """Compile one of the engine's paged steps as the engine jits it (the
    pool donated), at stablelm-2-1.6b widths cut to 4 layers, over a
    65-page pool: head_dim 64, so the pool packs two heads to a 128-lane
    row (``serving/pool.page_rows``).  Returns (compiled, pool shape)."""
    from repro.configs import get_config
    from repro.models.params import init_params
    from repro.models.transformer import model_for
    from repro.serving.pool import page_rows
    from repro.training import steps

    # the model's kernel wrapper asks the attached backend (the CPU here)
    # whether to interpret; this compile is for the described chip
    monkeypatch.setattr("repro.kernels.ops.interpret_mode", lambda: False)
    cfg = get_config("stablelm-1.6b").replace(num_layers=4, qkv_bias=True)
    model = model_for(cfg, remat="none")
    slots, page_size, num_pages = 4, 16, 65
    max_pages = (num_pages - 1) // slots
    pool = (cfg.num_layers, num_pages, page_size) + \
        page_rows(cfg.num_kv_heads, cfg.head_dim)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(model.param_table(),
                                           jax.random.PRNGKey(0))))
    cache = {"k": S(pool, jnp.bfloat16), "v": S(pool, jnp.bfloat16),
             "index": S((slots,), jnp.int32)}
    i32 = jnp.int32
    if step == "decode":
        fn = steps.build_decode_step_slots_paged(model, use_kernel=True)
        args = (params, cache, S((slots, 1), i32), S((slots,), i32),
                S((slots, max_pages), i32))
        jitted = jax.jit(fn, donate_argnums=(1,))
    elif step == "verify":
        fn = steps.build_verify_step_slots_paged(model)
        args = (params, cache, S((slots, 4), i32), S((slots,), i32),
                S((slots, max_pages), i32))
        jitted = jax.jit(fn, donate_argnums=(1,))
    else:
        # a one-token chunk too: XLA is freest to pick its own pool
        # layout for a one-row scatter
        fn = steps.build_prefill_chunk_step_paged(model)
        tokens = 1 if step == "chunk1" else 128
        args = (params, cache, S((1, tokens), i32), S((), i32), S((), i32),
                S((), i32), max_pages * page_size, S((max_pages,), i32))
        jitted = jax.jit(fn, donate_argnums=(1,), static_argnums=(6,))
    return jitted.lower(*args).compile(), pool


@pytest.mark.parametrize("step", ["decode", "chunk", "chunk1", "verify"])
def test_paged_step_keeps_the_pool_in_place(one_chip, monkeypatch, step):
    """The layer scan carries the whole pool: the compiled step aliases the
    donated K and V to its outputs, holds no copy shaped like the pool or
    like one layer's slice of it, and needs less scratch than one layer's
    K pool."""
    compiled, pool = _paged_step(one_chip, monkeypatch, step)
    text = compiled.as_text()
    if step == "decode":
        assert "tpu_custom_call" in text
    shapes = ["bf16[" + ",".join(map(str, dims)) + "]"
              for dims in (pool, pool[1:])]
    copies = [line.strip() for line in text.splitlines()
              if " copy(" in line and any(s in line for s in shapes)]
    assert copies == []
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(pool) * 2
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // pool[0]


# ---------------------------------------------------------------------------
# DeepSeek-V2-Lite: the latent-attention kernel and steps


def test_mla_decode_attention_compiles_at_real_widths(one_chip):
    """The cell's decode kernel: 64 slots x 256 pages of 16, 16 heads over
    640-lane latent rows (512 of them values), 27 layers of pool."""
    from repro.kernels.mla_attention import mla_decode_attention_pallas
    slots, heads, lanes, pages, layers = 64, 16, 640, 256, 27

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = _compile_text(
        lambda q, pool, pt, kl, ly: mla_decode_attention_pallas(
            q, pool, pt, kl, ly, scale=0.1, value_lanes=512,
            interpret=False),
        S((slots, heads, lanes), jnp.bfloat16),
        S((layers, slots * pages + 1, 16, lanes), jnp.bfloat16),
        S((slots, pages), jnp.int32), S((slots,), jnp.int32),
        S((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["decode", "chunk", "chunk1"])
def test_mla_step_keeps_the_latent_pool_in_place(one_chip, monkeypatch,
                                                  step):
    """DeepSeek-V2-Lite's decode and chunk steps at published widths, cut
    to its dense layer and 2 MoE layers holding 8 of 64 experts, donated
    as the engine jits them: the latent pool aliases the output, no copy
    shaped like it or like one layer of it, scratch under one layer."""
    from repro.configs import get_config
    from repro.models.params import init_params
    from repro.models.transformer import model_for
    from repro.serving.pool import page_stores
    from repro.training import steps

    monkeypatch.setattr("repro.kernels.ops.interpret_mode", lambda: False)
    cfg = get_config("deepseek-v2-lite").replace(num_layers=3,
                                                 experts_held=8)
    model = model_for(cfg, remat="none")
    slots, page_size, num_pages = 8, 16, 1025
    max_pages = (num_pages - 1) // slots

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(model.param_table(),
                                           jax.random.PRNGKey(0))))
    stores = jax.eval_shape(lambda: page_stores(cfg, num_pages, page_size))
    cache = {n: S(a.shape, a.dtype) for n, a in stores.items()}
    cache["index"] = S((slots,), jnp.int32)
    i32 = jnp.int32
    if step == "decode":
        fn = steps.build_decode_step_slots_paged(model, use_kernel=True)
        args = (params, cache, S((slots, 1), i32), S((slots,), i32),
                S((slots, max_pages), i32))
        jitted = jax.jit(fn, donate_argnums=(1,))
    else:
        fn = steps.build_prefill_chunk_step_paged(model)
        tokens = 1 if step == "chunk1" else 128
        args = (params, cache, S((1, tokens), i32), S((), i32), S((), i32),
                S((), i32), max_pages * page_size, S((max_pages,), i32))
        jitted = jax.jit(fn, donate_argnums=(1,), static_argnums=(6,))
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    if step == "decode":
        assert "tpu_custom_call" in text
    pool = stores["latent"].shape
    shapes = ["bf16[" + ",".join(map(str, dims)) + "]"
              for dims in (pool, pool[1:])]
    copies = [line.strip() for line in text.splitlines()
              if " copy(" in line and any(s in line for s in shapes)]
    assert copies == []
    mem = compiled.memory_analysis()
    pool_bytes = math.prod(pool) * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // pool[0]
