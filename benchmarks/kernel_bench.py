"""Kernel microbenchmarks: Pallas (interpret) vs reference, plus the
reference path timings that stand for the unfused baseline.  On CPU the
interpret-mode kernel is an emulation (correctness vehicle); the headline
number for the TPU target is the HBM-traffic reduction, reported by the
roofline pass — here we record wall times + bytes-moved estimates."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import (flash_attention, paged_attention, rmsnorm,
                               sedov_step_kernel)
from repro.models import lulesh


def _time(fn, *args, n=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def run(report) -> None:
    rng = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(rng, 3)

    b, s, H, K, dh = 1, 512, 4, 2, 64
    q = jax.random.normal(k1, (b, s, H, dh), jnp.float32)
    k = jax.random.normal(k2, (b, s, K, dh), jnp.float32)
    v = jax.random.normal(k3, (b, s, K, dh), jnp.float32)
    t_ref = _time(lambda *a: ref.attention_ref(*a), q, k, v)
    t_pal = _time(lambda *a: flash_attention(*a, causal=True), q, k, v)
    # HBM traffic: unfused materializes s^2 scores fp32 (x2 passes) + probs
    unfused_bytes = b * H * s * s * 4 * 3
    # fused touches q + out at H heads but K/V at only K kv heads (GQA)
    fused_bytes = (2 * H + 2 * K) * b * s * dh * 4
    report("kernel_flash_ref", t_ref * 1e6, f"bytes={unfused_bytes}")
    report("kernel_flash_pallas_interp", t_pal * 1e6,
           f"bytes={fused_bytes},traffic_reduction="
           f"{unfused_bytes / fused_bytes:.1f}x")

    # --- paged decode: gather-then-attend vs fused page-walk kernel -------
    # one decode tick over a heavy-tailed slot mix: the gather path
    # materializes every slot's WORST-CASE (max_pages*page_size) K/V run
    # through the page table before attending; the fused kernel streams
    # only the pages each slot actually holds (3 phases, never written)
    from repro.models.layers import dot_attention
    slots, psize, max_pages = 4, 16, 8
    Kp, dhp = 2, 64
    Hp = 4
    lens = [128, 48, 16, 96]                   # heavy-tailed slot lengths
    held = [-(-L // psize) for L in lens]
    num_pages = sum(held) + 1                  # + reserved junk page 0
    table = jnp.zeros((slots, max_pages), jnp.int32)
    nxt = 1
    for i, h in enumerate(held):
        table = table.at[i, :h].set(jnp.arange(nxt, nxt + h))
        nxt += h
    kv_lens = jnp.asarray(lens, jnp.int32)
    qd = jax.random.normal(k1, (slots, Hp, dhp), jnp.float32) \
        .astype(jnp.bfloat16)
    kp = jax.random.normal(k2, (num_pages, psize, Kp, dhp), jnp.float32) \
        .astype(jnp.bfloat16)
    vp = jax.random.normal(k3, (num_pages, psize, Kp, dhp), jnp.float32) \
        .astype(jnp.bfloat16)

    @jax.jit
    def gather_decode(qd, kp, vp, table, kv_lens):
        kg = jnp.take(kp, table, axis=0).reshape(
            slots, max_pages * psize, Kp, dhp)
        vg = jnp.take(vp, table, axis=0).reshape(
            slots, max_pages * psize, Kp, dhp)
        return dot_attention(qd[:, None], kg, vg, causal=True,
                             q_offset=kv_lens - 1, kv_len=kv_lens)

    t_gather = _time(gather_decode, qd, kp, vp, table, kv_lens)
    t_fused = _time(paged_attention, qd, kp, vp, table, kv_lens)
    item = 2                                   # bf16 K/V pool
    # gather: the materialized (slots, max_pages*psize, K, dh) K+V tensor
    # is written once and read back by attention
    gather_bytes = 2 * 2 * slots * max_pages * psize * Kp * dhp * item
    # fused: each held page's K and V copied from the pool once, no write
    fused_paged_bytes = 2 * sum(held) * psize * Kp * dhp * item
    report("kernel_paged_decode_gather", t_gather * 1e6,
           f"bytes={gather_bytes}")
    report("kernel_paged_decode_fused", t_fused * 1e6,
           f"bytes={fused_paged_bytes},traffic_reduction="
           f"{gather_bytes / fused_paged_bytes:.1f}x")

    x = jax.random.normal(k1, (4096, 2048), jnp.bfloat16)
    w = jnp.ones((2048,), jnp.float32)
    t_ref = _time(ref.rmsnorm_ref, x, w)
    t_pal = _time(rmsnorm, x, w)
    report("kernel_rmsnorm_ref", t_ref * 1e6, "bytes=5x")
    report("kernel_rmsnorm_pallas_interp", t_pal * 1e6, "bytes=2x")

    cfg = lulesh.LuleshConfig(grid=16)
    st = lulesh.init_state(cfg)
    t_ref = _time(lambda s_: lulesh.step(s_, cfg), st)
    t_pal = _time(lambda s_: sedov_step_kernel(s_, cfg, block_x=8), st)
    report("kernel_sedov_ref", t_ref * 1e6, "passes=8")
    report("kernel_sedov_pallas_interp", t_pal * 1e6, "passes=1")
