"""Pallas TPU fused paged-attention decode kernel (page-table walk in-kernel).

The paged serving decode path previously paid a full materialized gather
every tick: K/V were read back *through* the page table into a
(slots, max_pages*page_size, K, dh) tensor before attention — the same
unfused-HBM-traffic failure mode the roofline quantified for prefill
scores, now on the KV stream.  This kernel walks each slot's page-table
row *inside* the kernel instead (the PagedAttention design, vLLM): the
innermost grid dimension streams pages, each page's K/V block DMA'd
straight from the (num_pages, page_size, K, dh) pool via a
scalar-prefetched page-table index map, with the softmax statistics
carried across pages in VMEM scratch — the block/`pl.when` idiom of
kernels/flash_attention.py with the kv grid dimension redirected through
the page table.

Parity contract: the serving engine promises token-identical streams with
the kernel on or off, and the reference path (models/layers.dot_attention
over the gathered KV) rounds its *normalized* probabilities to the
activation dtype (bf16) before the PV contraction.  A single online
pass cannot reproduce that per-element rounding (probabilities are only
normalized at the very end), so the page walk runs in three phases over
the same page stream — max, denominator, then PV with the same
normalize-then-round sequence as the reference:

    phase 0   m   = max_t s_t                    (exact; order-free)
    phase 1   l   = sum_t exp(s_t - m)           (f32, page-sequential)
    phase 2   acc = sum_t round_bf16(exp(s_t - m) / l) * v_t   (f32)

Scratch (m, l, acc) carries across the whole 3 * max_pages walk; pages a
slot does not hold are skipped, so the pool is streamed at ~3x the
slot's *held* bytes — still far below the gather's materialized
worst-case (slots, max_pages*page_size, K, dh) read-plus-write on
heavy-tailed traces (see benchmarks/kernel_bench.py).

Layout/masking contract (mirrors models/layers.py's paged decode arm):

* the grid is (slots, 3 * max_pages); each step DMAs one whole page,
  ``(page_size, K, dh)`` over ALL kv heads.  The TPU compiler requires a
  block's last two dims to be multiples of (8, 128) or to equal the
  array's; a one-kv-head block ``(1, page_size, 1, dh)`` puts a size-1
  dim second from last and is refused, while ``(K, dh)`` equals the
  pool's own trailing dims at any width.  The heads are then processed
  side by side on the vector unit: scores are a lane reduction of
  ``k * q`` over dh, the PV product a sum over the page's tokens;
* the query block holds one slot's heads as ``(G, K, dh)`` (G = H // K
  query heads per kv head, transposed outside the kernel so each of the
  G groups is a plain ``(K, dh)`` slab lined up with the page's heads);
* token position ``ip * page_size + j`` is masked at each slot's own
  ``kv_len`` (per-slot lengths — continuous batching);
* page-table entries equal to 0 are the reserved junk page (freed /
  never-grown rows): their blocks are skipped entirely, so a freed
  slot's output is exactly zero rather than an average of dead writes;
* a fully-masked row cannot poison the accumulator: ``p`` is zeroed
  under the mask explicitly (NEG_INF - NEG_INF = 0 would otherwise make
  exp() emit 1 per masked key) and a slot with no live page never
  divides by its zero denominator.

Validated in interpret mode against the gather-then-attend oracle
(kernels/ref.paged_attention_ref) over a page_size x pages-per-slot x
GQA-ratio x per-slot-length sweep (tests/test_kernels_paged.py); compiled
for a described v5e at deepseek-7b widths (tests/test_tpu_compile.py);
checked compiled against the oracle on the chip by chip_smoke.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         page_size: int, max_pages: int, groups: int):
    """One (slot, phase*page) grid step of the fused decode attention.

    ``pt_ref``/``len_ref`` are the scalar-prefetched (slots, max_pages)
    page table and (slots,) kv lengths — prefetched so the k/v BlockSpec
    index maps can route each grid step's DMA to ``pt_ref[slot, page]``
    before the body runs.  The innermost grid dimension walks the page
    stream three times (max / denominator / PV — see module docstring);
    VMEM scratch carries (m, l, acc) across the whole walk (innermost is
    sequential on TPU).  Per query group g the state is ``m/l[g]`` of
    shape (K, 1) and ``acc[g]`` of shape (K, dh); scores are (page_size,
    K, 1) — token-major, heads on sublanes, so every reduction is either
    over lanes (dh) or over the leading token axis.
    """
    is_, it = pl.program_id(0), pl.program_id(1)
    ip = it % max_pages
    phase = it // max_pages

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    page = pt_ref[is_, ip]
    kv_len = len_ref[is_]

    # skip junk-page rows (page-table entry 0: freed slots, rows past the
    # slot's held pages) and pages wholly beyond the slot's length — the
    # whole block is masked, so there is nothing to accumulate
    live = (page != 0) & (ip * page_size < kv_len)

    def scores(g):
        q = q_ref[0, g].astype(jnp.float32)           # (K, dh)
        k = k_ref[0].astype(jnp.float32)              # (page_size, K, dh)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        pos = ip * page_size + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        return s, pos < kv_len                        # (page_size, K, 1)

    @pl.when(live & (phase == 0))
    def _max_pass():
        for g in range(groups):
            s, mask = scores(g)
            s = jnp.where(mask, s, NEG_INF)
            m_ref[g] = jnp.maximum(m_ref[g], jnp.max(s, axis=0))

    @pl.when(live & (phase == 1))
    def _sum_pass():
        for g in range(groups):
            s, mask = scores(g)
            # explicit zero under the mask: a row with no live key keeps
            # m = NEG_INF, and exp(s - m) = exp(NEG_INF - NEG_INF) = 1 for
            # the masked entries (the flash-kernel poisoning bug, fixed
            # there too)
            p = jnp.where(mask, jnp.exp(s - m_ref[g][None]), 0.0)
            l_ref[g] = l_ref[g] + jnp.sum(p, axis=0)

    @pl.when(live & (phase == 2))
    def _pv_pass():
        v = v_ref[0]                                  # (page_size, K, dh)
        for g in range(groups):
            s, mask = scores(g)
            p = jnp.where(mask, jnp.exp(s - m_ref[g][None]), 0.0)
            # normalize THEN round to the value dtype — the reference
            # path's probs.astype(v.dtype) before the PV contraction,
            # reproduced per element so kernel-on streams are
            # token-identical
            p = (p / l_ref[g][None]).astype(v.dtype)
            acc_ref[g] = acc_ref[g] + jnp.sum(
                p.astype(jnp.float32) * v.astype(jnp.float32), axis=0)

    @pl.when(it == 3 * max_pages - 1)
    def _finalize():
        # acc is already normalized; a slot with no live page at all
        # (freed / junk-only row) never entered the phases -> exact zero
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           kv_len: jax.Array, *,
                           interpret: bool = True) -> jax.Array:
    """Fused single-token decode attention over a paged KV pool.

    q: (slots, H, dh) — one new query token per slot;
    k_pages/v_pages: (num_pages, page_size, K, dh) page pool, H % K == 0;
    page_table: (slots, max_pages) int32 — entry 0 is the reserved junk
        page and is masked in-kernel;
    kv_len: (slots,) int32 valid tokens per slot (the new token included).
    Returns (slots, H, dh).

    interpret=True executes the kernel body on CPU (validation); on a
    real TPU pass interpret=False.
    """
    slots, H, dh = q.shape
    _, page_size, K, _ = k_pages.shape
    assert H % K == 0, (H, K)
    G = H // K
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == slots and kv_len.shape == (slots,), \
        (page_table.shape, kv_len.shape, slots)
    scale = 1.0 / math.sqrt(dh)
    # query head h = k * G + g  ->  (slots, G, K, dh): group-major, so
    # q_ref[0, g] is a (K, dh) slab matching the page's (K, dh) heads
    qg = q.reshape(slots, K, G, dh).transpose(0, 2, 1, 3)

    def kv_map(is_, it, pt, kl):
        # the page walk: this slot's (it mod max_pages)-th page, straight
        # from the pool — revisited once per phase
        return (pt[is_, it % max_pages], 0, 0, 0)

    def q_map(is_, it, pt, kl):
        return (is_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # page table + kv lengths
        grid=(slots, 3 * max_pages),
        in_specs=[
            pl.BlockSpec((1, G, K, dh), q_map),
            pl.BlockSpec((1, page_size, K, dh), kv_map),
            pl.BlockSpec((1, page_size, K, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, G, K, dh), q_map),
        scratch_shapes=[
            # VMEM scratch carrying softmax state across the page walk
            pltpu.VMEM((G, K, dh), jnp.float32),
            pltpu.VMEM((G, K, 1), jnp.float32),
            pltpu.VMEM((G, K, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, max_pages=max_pages,
                               groups=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, G, K, dh), q.dtype),
        interpret=interpret,
        # the op's name in a profiler trace, whatever wraps the call
        name="paged_attention",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3).reshape(slots, H, dh)
