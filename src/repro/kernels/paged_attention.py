"""Pallas TPU fused paged-attention decode kernel (page-table walk in-kernel).

The paged serving decode path previously paid a full materialized gather
every tick: K/V were read back *through* the page table into a
(slots, max_pages*page_size, K, dh) tensor before attention — the same
unfused-HBM-traffic failure mode the roofline quantified for prefill
scores, now on the KV stream.  This kernel walks each slot's page-table
row *inside* the kernel instead (the PagedAttention design, vLLM): the
innermost grid dimension streams pages, each page's K/V block DMA'd
straight from the (layers, num_pages, page_size, rows, lanes) pool via a
scalar-prefetched page-table index map, with the softmax statistics
carried across pages in VMEM scratch — the block/`pl.when` idiom of
kernels/flash_attention.py with the kv grid dimension redirected through
the page table.

The pool is every layer's, as the decode step's layer scan carries it:
the layer id is a third scalar prefetch and the first coordinate of the
K/V index map, so one layer's pages are read where they lie and the scan
never slices (copies) a layer's pool out for the call.  A one-layer pool
(num_pages, page_size, rows, lanes) is read as layer 0 of a one-layer
stack.  A token's K (or V) is ``(rows, lanes)``: its kv heads ``(K,
dh)``, or, where dh is under 128, ``128 // dh`` heads side by side in a
128-lane row (``serving/pool.page_rows``) — the layout a TPU keeps such
a pool in by default, dense, where a ``(K, 64)`` pool would be laid out
page-minor and need a whole-pool relayout before every call.  A packed
row's heads are told apart by lane (each head's dot product a masked
lane sum); the passes and their rounding are those of one head to a row.

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

* the grid is (slots, 3 * max_pages); each step DMAs one whole page of
  one layer, ``(page_size, rows, lanes)`` over ALL kv heads (block
  ``(None, 1, page_size, rows, lanes)``: the layer dim squeezed).  The
  TPU compiler requires a block's last two dims to be multiples of
  (8, 128) or to equal the array's; a one-kv-head block
  ``(1, page_size, 1, dh)`` puts a size-1 dim second from last and is
  refused, while ``(rows, lanes)`` equals the pool's own trailing dims
  at any width.  The heads are then processed
  side by side on the vector unit: scores are a lane reduction of
  ``k * q`` over dh, the PV product a sum over the page's tokens;
* the query block holds one slot's heads as ``(G, rows, lanes)`` (G =
  H // K query heads per kv head, transposed and packed outside the
  kernel so each of the G groups is a slab lined up with the page's
  heads);
* token position ``ip * page_size + j`` is masked at each slot's own
  ``kv_len`` (per-slot lengths — continuous batching);
* page-table entries equal to 0 are the reserved junk page (freed /
  never-grown rows; each layer has its own page 0): their blocks are
  skipped entirely, so a freed
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


def _paged_decode_kernel(pt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                         page_size: int, max_pages: int, groups: int,
                         head_dim: int):
    """One (slot, phase*page) grid step of the fused decode attention.

    ``pt_ref``/``len_ref``/``layer_ref`` are the scalar-prefetched
    (slots, max_pages) page table, (slots,) kv lengths and (1,) layer —
    prefetched so the k/v BlockSpec index maps can route each grid step's
    DMA to ``(layer_ref[0], pt_ref[slot, page])`` before the body runs
    (the body itself never reads the layer).  The innermost grid
    dimension walks the page stream three times (max / denominator / PV
    — see module docstring); VMEM scratch carries (m, l, acc) across the
    whole walk (innermost is sequential on TPU).  A page row holds
    ``r = lanes // head_dim`` kv heads side by side, head j of a row in
    lanes ``[j * head_dim, (j + 1) * head_dim)`` (r = 1: a row is one
    head).  Per query group g and row head j the state is ``m/l[g, j]``
    of shape (rows, 1), and ``acc[g]`` is (rows, lanes); scores are
    (page_size, rows, 1) — token-major, heads on sublanes, so every
    reduction is either over lanes or over the leading token axis.
    """
    is_, it = pl.program_id(0), pl.program_id(1)
    ip = it % max_pages
    phase = it // max_pages
    r = k_ref.shape[-1] // head_dim

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

    def head_of_lane(shape):
        return jax.lax.broadcasted_iota(jnp.int32, shape, 2) // head_dim

    def scores(g):
        """[s_j for each row head j], mask — each (page_size, rows, 1)."""
        q = q_ref[0, g].astype(jnp.float32)           # (rows, lanes)
        k = k_ref[0].astype(jnp.float32)              # (page_size, rows, lanes)
        qk = k * q[None]
        if r == 1:
            parts = [qk]
        else:
            head = head_of_lane(qk.shape)
            parts = [jnp.where(head == j, qk, 0.0) for j in range(r)]
        ss = [jnp.sum(x, axis=-1, keepdims=True) * scale for x in parts]
        pos = ip * page_size + \
            jax.lax.broadcasted_iota(jnp.int32, ss[0].shape, 0)
        return ss, pos < kv_len

    @pl.when(live & (phase == 0))
    def _max_pass():
        for g in range(groups):
            ss, mask = scores(g)
            for j, s in enumerate(ss):
                s = jnp.where(mask, s, NEG_INF)
                m_ref[g, j] = jnp.maximum(m_ref[g, j], jnp.max(s, axis=0))

    @pl.when(live & (phase == 1))
    def _sum_pass():
        for g in range(groups):
            ss, mask = scores(g)
            for j, s in enumerate(ss):
                # explicit zero under the mask: a row with no live key keeps
                # m = NEG_INF, and exp(s - m) = exp(NEG_INF - NEG_INF) = 1
                # for the masked entries (the flash-kernel poisoning bug,
                # fixed there too)
                p = jnp.where(mask, jnp.exp(s - m_ref[g, j][None]), 0.0)
                l_ref[g, j] = l_ref[g, j] + jnp.sum(p, axis=0)

    @pl.when(live & (phase == 2))
    def _pv_pass():
        v = v_ref[0]                                  # (page_size, rows, lanes)
        for g in range(groups):
            ss, mask = scores(g)
            probs = None
            for j, s in enumerate(ss):
                p = jnp.where(mask, jnp.exp(s - m_ref[g, j][None]), 0.0)
                # normalize THEN round to the value dtype — the reference
                # path's probs.astype(v.dtype) before the PV contraction,
                # reproduced per element so kernel-on streams are
                # token-identical
                p = (p / l_ref[g, j][None]).astype(v.dtype).astype(
                    jnp.float32)
                if r == 1:
                    probs = p
                else:
                    # row head j's probabilities across its own lanes
                    if probs is None:
                        head = head_of_lane(v.shape)
                        probs = jnp.zeros(v.shape, jnp.float32)
                    probs = jnp.where(head == j, p, probs)
            acc_ref[g] = acc_ref[g] + jnp.sum(
                probs * v.astype(jnp.float32), axis=0)

    @pl.when(it == 3 * max_pages - 1)
    def _finalize():
        # acc is already normalized; a slot with no live page at all
        # (freed / junk-only row) never entered the phases -> exact zero
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


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
    K = rows * (lanes // dh)
    assert H % K == 0, (H, K)
    G = H // K
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == slots and kv_len.shape == (slots,), \
        (page_table.shape, kv_len.shape, slots)
    scale = 1.0 / math.sqrt(dh)
    # query head h = k * G + g  ->  (slots, G, K, dh): group-major, so
    # q_ref[0, g] is a slab of the K heads laid out as the page's rows
    qg = q.reshape(slots, K, G, dh).transpose(0, 2, 1, 3).reshape(
        slots, G, rows, lanes)

    def kv_map(is_, it, pt, kl, ly):
        # the page walk: this slot's (it mod max_pages)-th page of layer
        # ly, straight from the pool — revisited once per phase
        return (ly[0], pt[is_, it % max_pages], 0, 0, 0)

    def q_map(is_, it, pt, kl, ly):
        return (is_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # page table + kv lengths + layer
        grid=(slots, 3 * max_pages),
        in_specs=[
            pl.BlockSpec((1, G, rows, lanes), q_map),
            pl.BlockSpec((None, 1, page_size, rows, lanes), kv_map),
            pl.BlockSpec((None, 1, page_size, rows, lanes), kv_map),
        ],
        out_specs=pl.BlockSpec((1, G, rows, lanes), q_map),
        scratch_shapes=[
            # VMEM scratch carrying softmax state across the page walk
            pltpu.VMEM((G, rows, lanes), jnp.float32),
            pltpu.VMEM((G, lanes // dh, rows, 1), jnp.float32),
            pltpu.VMEM((G, lanes // dh, rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, max_pages=max_pages,
                               groups=G, head_dim=dh)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, G, rows, lanes), q.dtype),
        interpret=interpret,
        # the op's name in a profiler trace, whatever wraps the call
        name="paged_attention",
    )(page_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(slots, G, K, dh).transpose(0, 2, 1, 3).reshape(
        slots, H, dh)
