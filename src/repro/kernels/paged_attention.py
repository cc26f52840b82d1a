"""Pallas TPU fused paged-attention decode kernel: one pass over live pages.

The paged serving decode reads each slot's K and V through its page table
inside the kernel (the PagedAttention design, vLLM), so the gathered
``(slots, max_pages*page_size, K, dh)`` tensor never hits HBM.

The grid is one step per slot.  Each step walks the slot's live pages in
blocks of ``ppb`` pages: every live page's K and V are copied from the
pool in HBM (``memory_space=ANY``, read in place at the scalar-prefetched
``layer``: no slice of the pool is materialised) to VMEM buffers by their
own DMAs, double-buffered, so the next block's copies run while this
block is computed, and a slot's last block starts the next slot's first.
Each live page is copied once; a page past the slot's length, or behind a
page-table entry of 0 (the reserved junk page: freed slots, rows past the
held run), is never copied.  ``ppb`` follows from the page's size: about
``2**19`` elements (1 MiB of bf16) of K per buffer, at most 16 pages and
at most the table's width.  The buffers are zeroed once per call, so rows
a block does not load hold finite stale values, which the mask drops.

One online-softmax pass carries ``(m, l, acc)`` in float32 across the
blocks and normalises once at the end.  A token's K (or V) is ``(rows,
lanes)``: its kv heads ``(K, dh)``, or ``lanes // dh`` heads side by side
in a 128-lane row (``serving/pool.page_rows``), the layout a TPU keeps
such a pool in.  A block's ``ppb * page_size`` tokens are a ``(N, lanes)``
matrix of ``N = ppb * page_size * rows`` rows, and the queries are an
``(M, lanes)`` matrix of ``M = G * (lanes // dh) * rows`` rows: query
group g of the kv head in row r, lane slot j, in row ``(g, j, r)``, its
dh lanes at slot j and zeros elsewhere.  So the scores of every head are
one matrix product on the MXU, ``(M, N)``, in which row ``(g, j, r)``
meets its own kv head at the columns of row r and the rest are masked;
the weighted sum of V is a second product, ``(M, N) x (N, lanes)``, whose
row ``(g, j, r)`` is kept at the lanes of slot j.

Masking: a column is live where its token is below the slot's ``kv_len``
and its page-table entry is not 0; ``p`` is zeroed under the mask
explicitly (NEG_INF - NEG_INF = 0 would otherwise make exp() emit 1 per
masked key), and a slot with no live page writes exact zeros.

Numerics: the inputs stay in the pool's dtype and every sum is float32.
The probabilities meet V unnormalised, rounded to V's dtype, as in
``kernels/mla_attention.py``; the gather path (``models/layers.
dot_attention``) rounds the normalised ones, so the two agree to the
dtype's rounding, not bit for bit.  Validated in interpret mode against
the gather oracle (``kernels/ref.paged_attention_ref``,
tests/test_kernels_paged.py); compiled for a described v5e at
deepseek-7b and stablelm-2-1.6b widths (tests/test_tpu_compile.py).

The ``pallas_call`` is named ``paged_attention``; a profiler trace shows
its ops under that name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _split(x, d: int):
    """(x // d, x % d) of a non-negative int32 array."""
    if d & (d - 1) == 0:
        return x >> (d.bit_length() - 1), x & (d - 1)
    return jax.lax.div(x, d), jax.lax.rem(x, d)


def _paged_decode_kernel(len_ref, pt_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sem, state, *, scale: float,
                         page_size: int, max_pages: int, ppb: int,
                         groups: int, head_dim: int):
    """One slot: its live pages, ``ppb`` at a time.

    ``len_ref``, ``pt_ref`` (the page table, flattened) and ``layer_ref``
    are scalar-prefetched.  ``state`` (SMEM, int32) holds the buffer the
    next block lands in (0 or 1) and whether this slot's first block was
    started by the step before it."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    layer = layer_ref[0]
    _, _, _, rows, lanes = k_buf.shape
    bk = ppb * page_size
    n_cols = bk * rows

    def length(slot):
        return jnp.minimum(len_ref[slot], max_pages * page_size)

    def page_of(slot, idx):
        """The page-table entry of page ``idx`` of ``slot`` and whether it
        is live (below the slot's length, not the junk page)."""
        page = pt_ref[slot * max_pages + jnp.minimum(idx, max_pages - 1)]
        n_pages = (length(slot) + page_size - 1) // page_size
        return page, (idx < n_pages) & (page != 0)

    def copies(slot, blk, which):
        out = []
        for j in range(ppb):
            page, live = page_of(slot, blk * ppb + j)
            for kv, (src, dst) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                out.append((live, pltpu.make_async_copy(
                    src.at[layer, page], dst.at[which, j], sem.at[kv, which])))
        return out

    def start(slot, blk, which):
        for live, cp in copies(slot, blk, which):
            pl.when(live)(cp.start)

    def wait(slot, blk, which):
        for live, cp in copies(slot, blk, which):
            pl.when(live)(cp.wait)

    @pl.when(b == 0)
    def _first():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0
        state[1] = 0

    kv_len = length(b)
    nb = (kv_len + bk - 1) // bk

    @pl.when((nb > 0) & (state[1] == 0))
    def _start_own():
        start(b, 0, state[0])

    q = q_ref[0]                                        # (M, lanes)
    # query row (g, j, r) meets the kv head of its own row r
    own = _split(jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], n_cols), 0),
                 rows)[1] == _split(jax.lax.broadcasted_iota(
                     jnp.int32, (q.shape[0], n_cols), 1), rows)[1]
    tok, _ = _split(jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1), rows)
    page_in_block, _ = _split(tok, page_size)

    def attend(i, live_pages, carry):
        m, l, acc = carry
        which = state[0]
        k = k_buf[which].reshape(n_cols, lanes)
        v = v_buf[which].reshape(n_cols, lanes)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (M, N)
        col_live = (i * bk + tok < kv_len) & \
            (((live_pages >> page_in_block) & 1) == 1)
        mask = own & col_live
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    def body(i, carry):
        which = state[0]
        wait(b, i, which)
        nxt = 1 - which

        @pl.when(i + 1 < nb)
        def _next_block():
            start(b, i + 1, nxt)

        @pl.when((i + 1 == nb) & (b + 1 < slots))
        def _next_slot():
            start(jnp.minimum(b + 1, slots - 1), 0, nxt)

        # bit j: page j of the block is live (copied this block)
        live_pages = 0
        for j in range(ppb):
            live_pages = live_pages | jnp.where(
                page_of(b, i * ppb + j)[1], 1 << j, 0)
        carry = jax.lax.cond(live_pages != 0,
                             functools.partial(attend, i, live_pages),
                             lambda c: c, carry)
        state[0] = nxt
        return carry

    heads = q.shape[0]
    init = (jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, lanes), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, nb, body, init)
    # a slot with no live row keeps l = 0 and acc = 0: its output is zero
    o = acc / jnp.where(l > 0, l, 1.0)
    slot_heads = lanes // head_dim
    o = o.reshape(groups, slot_heads, rows, lanes)
    if slot_heads == 1:
        out = o[:, 0]
    else:
        lane_slot = jax.lax.broadcasted_iota(
            jnp.int32, (groups, rows, lanes), 2) // head_dim
        out = jnp.zeros((groups, rows, lanes), jnp.float32)
        for j in range(slot_heads):
            out = jnp.where(lane_slot == j, o[:, j], out)
    o_ref[0] = out.astype(o_ref.dtype)
    # the next slot's first block was started above when this one had any
    state[1] = ((nb > 0) & (b + 1 < slots)).astype(jnp.int32)


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           kv_len: jax.Array, layer: jax.Array | None = None,
                           *, interpret: bool = True) -> jax.Array:
    """Fused single-token decode attention over a paged KV pool.

    q: (slots, H, dh) — one new query token per slot;
    k_pages/v_pages: the page pool, one token's K kv heads stored as
        ``(rows, lanes)`` — ``(K, dh)``, or ``lanes // dh`` heads side by
        side in a row (``serving/pool.page_rows``), so K = rows * lanes //
        dh and H % K == 0 — either every layer's, (layers, num_pages,
        page_size, rows, lanes), read at layer ``layer``, or one layer's,
        (num_pages, page_size, rows, lanes), with no ``layer``;
    page_table: (slots, max_pages) int32 — entry 0 is the reserved junk
        page (of the layer read) and is masked in-kernel;
    kv_len: (slots,) int32 valid tokens per slot (the new token included);
    layer: int32 scalar, the layer of a 5-D pool (scalar-prefetched, so
        the K/V DMAs read that layer's pages in place: no slice of the
        pool is ever materialized).
    Returns (slots, H, dh).

    interpret=True executes the kernel body on CPU (validation); on a
    real TPU pass interpret=False.
    """
    if k_pages.ndim == 4:
        assert layer is None, "a one-layer pool takes no layer"
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    slots, H, dh = q.shape
    _, _, page_size, rows, lanes = k_pages.shape
    assert lanes % dh == 0, (lanes, dh)
    slot_heads = lanes // dh
    K = rows * slot_heads
    assert H % K == 0, (H, K)
    G = H // K
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == slots and kv_len.shape == (slots,), \
        (page_table.shape, kv_len.shape, slots)
    # about 2**19 elements of K per buffer (1 MiB of bf16), at most 16
    # pages (a block's live pages are the bits of an int32)
    ppb = max(1, min(max_pages, 16, 2**19 // (page_size * rows * lanes)))
    heads = G * slot_heads * rows
    # query head h = k * G + g of kv head k = r * slot_heads + j -> row
    # (g, j, r) of the query matrix, its dh lanes at lane slot j
    qg = q.reshape(slots, rows, slot_heads, G, dh).transpose(0, 3, 2, 1, 4)
    eye = jnp.eye(slot_heads, dtype=q.dtype)
    qm = (qg[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
          ).reshape(slots, heads, lanes)
    kernel = functools.partial(
        _paged_decode_kernel, scale=1.0 / math.sqrt(dh), page_size=page_size,
        max_pages=max_pages, ppb=ppb, groups=G, head_dim=dh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # kv lengths, page table, layer
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((1, heads, lanes), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, G, rows, lanes),
                               lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, rows, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, rows, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, G, rows, lanes), q.dtype),
        # slots run in order: each one's last block starts the next's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the op's name in a profiler trace, whatever wraps the call
        name="paged_attention",
    )(kv_len.astype(jnp.int32), page_table.reshape(-1).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qm, k_pages, v_pages)
    return out.reshape(slots, G, K, dh).transpose(0, 2, 1, 3).reshape(
        slots, H, dh)
