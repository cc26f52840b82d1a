"""Pallas TPU decode kernel for multi-head latent attention (MLA) over a
paged latent pool.

DeepSeek-V2's attention caches one latent row per token and layer: the
normalised ``c_kv`` (``kv_lora_rank`` lanes) and the rotated ``k_pe``
(``qk_rope_head_dim`` lanes), zero-padded to a whole number of 128-lane
tiles (``models/mla.latent_lanes``).  Decode absorbs the key and value
up-projections into the query and the output (``models/mla.py``), so
every query head attends the same latent rows:

    s_t   = scale * q . row_t            (q: the absorbed query, all lanes)
    o     = sum_t softmax(s)_t * row_t[:kv_lora_rank]

which is multi-query attention with one shared kv head whose keys are the
whole row and whose values are its first ``kv_lora_rank`` lanes.

The grid is one step per slot.  Each step walks the slot's live pages in
blocks of ``pages_per_block`` pages: every page of a block is copied from
the pool in HBM (``memory_space=ANY``, read in place at ``layer``) to a
VMEM buffer by its own DMA, double-buffered, so the next block's copies
run while this block is computed, and the last block of a slot starts the
first block of the next slot.  Only live pages are copied: each live page
is read once, pages past the slot's length never.  One online-softmax
pass carries ``(m, l, acc)`` across the blocks in registers; the scores
and the weighted sum are matrix products of the heads against the block's
rows, on the MXU.

Rows past a slot's length are masked (``p`` zeroed under the mask), a
slot of length 0 (free, or mid-prefill) writes zeros, and the buffer is
zeroed once per call, so rows a block does not load are finite.

The ``pallas_call`` is named ``mla_decode_attention``; a profiler trace
shows its ops under that name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# pages copied per block: 16 pages of 16 tokens read 256 rows per step
PAGES_PER_BLOCK = 16


def _mla_decode_kernel(len_ref, pt_ref, layer_ref, q_ref, pool_ref, o_ref,
                       buf_ref, sem_ref, state_ref, *, scale: float,
                       page_size: int, max_pages: int, ppb: int,
                       value_lanes: int):
    """One slot: its live pages, ``ppb`` at a time.

    ``state_ref`` (SMEM, int32) holds the buffer the next block lands in
    (0 or 1) and whether this slot's first block was started by the step
    before it."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    bk = ppb * page_size

    def n_pages(slot):
        return (len_ref[slot] + page_size - 1) // page_size

    def copies(slot, blk, which):
        """The DMA of each live page of block ``blk`` of ``slot``, with
        whether it is live (the same condition at start and at wait)."""
        out = []
        for j in range(ppb):
            idx = blk * ppb + j
            live = idx < n_pages(slot)
            page = pt_ref[slot * max_pages + jnp.minimum(idx, max_pages - 1)]
            out.append((live, pltpu.make_async_copy(
                pool_ref.at[layer, page], buf_ref.at[which, j],
                sem_ref.at[which])))
        return out

    def start(slot, blk, which):
        for live, cp in copies(slot, blk, which):
            pl.when(live)(cp.start)

    def wait(slot, blk, which):
        for live, cp in copies(slot, blk, which):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        buf_ref[...] = jnp.zeros_like(buf_ref)
        state_ref[0] = 0
        state_ref[1] = 0

    nb = (len_ref[b] + bk - 1) // bk

    @pl.when((nb > 0) & (state_ref[1] == 0))
    def _start_own():
        start(b, 0, state_ref[0])

    q = q_ref[0]                                        # (H, lanes)
    heads = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        which = state_ref[0]
        wait(b, i, which)
        nxt = 1 - which

        @pl.when(i + 1 < nb)
        def _next_block():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == nb) & (b + 1 < slots))
        def _next_slot():
            @pl.when(len_ref[jnp.minimum(b + 1, slots - 1)] > 0)
            def _():
                start(b + 1, 0, nxt)

        rows = buf_ref[which].reshape(bk, -1)           # (bk, lanes)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, bk)
        pos = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < len_ref[b]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_lanes],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        state_ref[0] = nxt
        return m_new, l, alpha * acc + pv

    init = (jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, value_lanes), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, nb, body, init)
    # a slot with no live row keeps l = 0 and acc = 0: its output is zero
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
    # the next slot's first block was started above when it has rows
    state_ref[1] = jnp.where(
        (nb > 0) & (b + 1 < slots),
        (len_ref[jnp.minimum(b + 1, slots - 1)] > 0).astype(jnp.int32), 0)


def mla_decode_attention_pallas(q: jax.Array, pool: jax.Array,
                                page_table: jax.Array, kv_len: jax.Array,
                                layer: jax.Array | int, *, scale: float,
                                value_lanes: int,
                                pages_per_block: int = PAGES_PER_BLOCK,
                                interpret: bool = True) -> jax.Array:
    """Single-token latent attention of every slot over its pages.

    q: (slots, H, lanes) — the absorbed queries, laid out as a latent row
        (zero in the pad lanes);
    pool: (layers, num_pages, page_size, lanes), read at ``layer``;
    page_table: (slots, max_pages) int32; kv_len: (slots,) int32 live rows
        per slot (the new token included; 0 for a slot not decoding);
    value_lanes: the lanes of a row that are values (``kv_lora_rank``).
    Returns (slots, H, value_lanes): each head's softmax-weighted sum of
    the value lanes.
    """
    slots, heads, row_lanes = q.shape
    _, _, page_size, pool_lanes = pool.shape
    assert pool_lanes == row_lanes, (pool.shape, q.shape)
    max_pages = page_table.shape[1]
    ppb = min(pages_per_block, max_pages)
    kernel = functools.partial(
        _mla_decode_kernel, scale=scale, page_size=page_size,
        max_pages=max_pages, ppb=ppb, value_lanes=value_lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # lengths, page table, layer
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, row_lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, heads, value_lanes),
                               lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, row_lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, value_lanes), q.dtype),
        # slots run in order: each one's last block starts the next's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(kv_len.astype(jnp.int32), page_table.reshape(-1).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, pool)
