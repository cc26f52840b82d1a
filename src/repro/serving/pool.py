"""The paged KV cache pool — the serving stack's memory layer.

``PagedKVCachePool`` stores KV as a pool of fixed-size pages plus a
per-slot page-table indirection,

    k, v      : (layers, num_pages, page_size, rows, lanes)
    index     : (num_slots,) int32 — tokens written per slot
    page_table: (num_slots, max_pages) int32 — host-side, shipped to the
                decode step each iteration as a plain argument

where one token's ``(kv_heads, head_dim)`` is stored as ``(rows, lanes)``
(``page_rows``: the heads themselves, or several heads to a 128-lane row
where head_dim is smaller).  A latent-attention model (``kv_lora_rank``)
stores one array instead of K and V, ``latent: (layers, num_pages,
page_size, lanes)``, a token's latent row padded to 128-lane tiles
(``models/mla.latent_lanes``); an MoE model's pool also carries
``route_counts`` (num_experts,) int32, the tokens its steps have routed
to each expert, added up on the device (``page_stores``).  A request
only ever holds ``ceil(len / page_size)`` pages, so the tuner's HBM
budget buys admitted *tokens*, not admitted worst cases.  Page 0 is a
reserved junk page: inactive slots (zeroed page-table rows) scatter
their dead writes there and nothing ever reads it through a live page
table.  Pages grow on demand during decode (``prepare_decode``); when
the pool is out of pages the scheduler preempts a request and resumes
it later.

Pages are **refcounted** (``page_refs``): normally a page has one owner
and ``free`` returns it immediately, but an attached shared-prefix cache
(``serving/prefix_cache.PrefixCache``) lets several requests — and the
cache itself — reference one page at once.  ``free`` then only
*decrements*; the page rejoins the free list at refcount zero, so a
preempted sharer can never free a page another request still reads.
Under page pressure the allocator reclaims cache-only pages (LRU) before
reporting starvation.

The pool hands out slots and pages from deterministic LIFO free lists
with an O(1) boolean free-mask (no linear membership scans), and rides
whole through one slot-wise decode step per iteration so requests of
different lengths share every matmul.  Buffers are donated on both the
insert and the decode path; the engine swaps the tree via ``update``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


class PoolExhausted(RuntimeError):
    """alloc() on a pool with no free slots / no free pages."""


class _FreeList:
    """Deterministic LIFO free list with an O(1) boolean free-mask.

    ``pop()`` hands out the lowest index first on a fresh pool; a freed
    index is the next one reissued (cache-friendly, reproducible).  The
    mask replaces the old O(n) ``idx in list`` membership scan on free.
    """

    def __init__(self, n: int, start: int = 0):
        self._items = list(range(n - 1 + start, start - 1, -1))
        self._mask = np.zeros((n + start,), bool)
        self._mask[start:] = True
        self.start = start

    def __len__(self) -> int:
        return len(self._items)

    def pop(self) -> int:
        idx = self._items.pop()
        self._mask[idx] = False
        return idx

    def push(self, idx: int) -> None:
        if self._mask[idx]:
            raise ValueError(f"index {idx} is already free")
        self._mask[idx] = True
        self._items.append(idx)

    def is_free(self, idx: int) -> bool:
        return bool(self._mask[idx])


def _check_servable(cfg):
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"KV pools serve attention-cache families (dense/moe), "
            f"not {cfg.family!r}")
    if cfg.window:
        raise NotImplementedError(
            "slot-wise decode does not apply sliding-window attention "
            "yet; a windowed config served here would silently attend "
            "the full history")


LANES = 128     # a TPU vector register's lanes: the minor dim of a tile


def page_rows(num_kv_heads: int, head_dim: int) -> tuple:
    """How a page pool stores one token's K (or V): ``(rows, lanes)``.

    ``(num_kv_heads, head_dim)`` itself where head_dim fills the lanes;
    where it is under 128 and divides it, ``128 // head_dim`` heads side
    by side in each 128-lane row (head ``h`` in row ``h // r``, lanes
    ``(h % r) * head_dim`` on) — the same bytes in the same order.  A
    TPU lays out a (kv_heads, 64) pool with the page axis minor-most
    (no lane padding), which the paged kernel cannot read a page of: every
    step would relayout the whole pool.  A 128-lane row is laid out
    row-major, dense, and read a page at a time in place.
    """
    width = num_kv_heads * head_dim
    if 0 < head_dim < LANES and LANES % head_dim == 0 and width % LANES == 0:
        return width // LANES, LANES
    return num_kv_heads, head_dim


def page_stores(cfg, num_pages: int, page_size: int) -> dict:
    """The arrays a paged pool holds for ``cfg`` besides its index: K and
    V, or the latent rows, one stack per layer, and an MoE model's
    routing counters."""
    lead = (cfg.num_layers, num_pages, page_size)
    if cfg.kv_lora_rank:
        from repro.models.mla import latent_lanes
        out = {"latent": jnp.zeros(lead + (latent_lanes(cfg),),
                                   cfg.activation_dtype)}
    else:
        shape = lead + page_rows(cfg.num_kv_heads, cfg.head_dim)
        out = {"k": jnp.zeros(shape, cfg.activation_dtype),
               "v": jnp.zeros(shape, cfg.activation_dtype)}
    if cfg.family == "moe":
        out["route_counts"] = jnp.zeros((cfg.num_experts,), jnp.int32)
    return out


@partial(jax.jit, donate_argnums=(0,))
def _scatter_insert(cache, slot, pages_row, prefill):
    """Write a batch-1 prefill cache — each store (L, 1, s, ...) — through
    `pages_row`.

    Token position j lands in page ``pages_row[j // page_size]`` at offset
    ``j % page_size`` — the same indirection the decode step reads back.
    """
    out = dict(cache)
    for name, new in prefill.items():
        L, _, s = new.shape[:3]
        shape = cache[name].shape
        P, psize, row = shape[1], shape[2], shape[3:]
        pos = jnp.arange(s)
        fpos = pages_row[pos // psize] * psize + pos % psize  # (s,)
        out[name] = cache[name].reshape((L, P * psize) + row).at[
            :, fpos].set(new[:, 0].reshape((L, s) + row)).reshape(shape)
    out["index"] = cache["index"].at[slot].set(s)
    return out


class PagedKVCachePool:
    """Page-table KV pool: slots hold page lists, not max_len reservations.

    ``num_pages`` counts the whole pool *including* the reserved junk page
    0, so ``num_pages - 1`` pages are allocatable.  A slot may hold at most
    ``max_pages = ceil(max_len / page_size)`` pages.  The page table lives
    on the host (alloc / free are pure bookkeeping, no device traffic) and
    is shipped to the decode step as a small int32 array each iteration.
    """

    def __init__(self, model, num_slots: int, max_len: int,
                 page_size: int = 16, num_pages: int = 0):
        cfg = model.cfg
        _check_servable(cfg)
        if num_slots < 1 or max_len < 1 or page_size < 1:
            raise ValueError((num_slots, max_len, page_size))
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.page_size = page_size
        self.max_pages = math.ceil(max_len / page_size)
        # default: worst case (every slot at max_len) + the junk page —
        # the tuner passes a budget-derived (smaller) pool instead
        self.num_pages = num_pages or num_slots * self.max_pages + 1
        if self.num_pages < 2:
            raise ValueError(f"num_pages {self.num_pages} < 2 "
                             f"(page 0 is reserved)")
        self.cache = dict(page_stores(cfg, self.num_pages, page_size),
                          index=jnp.zeros((num_slots,), jnp.int32))
        self.page_table = np.zeros((num_slots, self.max_pages), np.int32)
        self._pages_held = np.zeros((num_slots,), np.int64)
        self._free = _FreeList(num_slots)
        self._free_pages = _FreeList(self.num_pages - 1, start=1)
        self.lengths = np.zeros((num_slots,), np.int64)  # host mirror
        # owners per page: the allocating request, each prefix-cache
        # sharer, and the cache cell itself each hold one reference.
        # page_cached flags cache-pinned pages and _cache_only counts the
        # ones no request shares (refcount exactly 1) — maintained on the
        # 1<->2 refcount transitions so the admission/load-signal hot
        # paths never scan the cache.
        self.page_refs = np.zeros((self.num_pages,), np.int32)
        self.page_cached = np.zeros((self.num_pages,), bool)
        self._cache_only = 0
        self.prefix_cache = None     # attached by PrefixCache(pool, ...)

    # -- capacity ----------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def reclaimable_pages(self) -> int:
        """Pages the attached prefix cache could hand back on demand
        (cache-pinned, shared with no live request) — spendable headroom
        for admission and the router's load signal.  O(1): a running
        count, not a cache scan."""
        return self._cache_only

    @property
    def free_tokens(self) -> int:
        """Admittable KV tokens left — the load signal a router's
        least-loaded policy balances: free pages worth of tokens, gated on
        a free page-table row existing at all.  Cache-only prefix pages
        count as free — they are reclaimed before anything starves — and
        a page shared by N requests is simply not free, so the signal
        never double-counts it."""
        if not self.num_free:
            return 0
        return (self.free_pages + self.reclaimable_pages) * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.page_size)

    def can_admit(self, prompt_len: int, active_slots=(),
                  hit=None) -> bool:
        """Admission needs a slot, pages for the prompt, and headroom for
        the in-flight requests that are about to cross a page boundary —
        reserving those avoids admit/preempt ping-pong under pressure.

        With a prefix-cache ``hit`` only the cold suffix's pages must be
        found: the shared run is already resident.  Spendable headroom is
        free pages plus what the cache can reclaim, *minus* the hit's
        cache-only pages — attaching pins those, so counting them as
        reclaimable too would promise the same page twice."""
        if self.num_free == 0 or prompt_len > self.max_len:
            return False
        imminent = sum(
            1 for s in active_slots
            if self.lengths[s] >= self._pages_held[s] * self.page_size)
        need = self.pages_for(prompt_len)
        avail = self.free_pages + self.reclaimable_pages
        if hit is not None and hit.pages:
            need -= len(hit.pages)
            avail -= hit.pinned
        return avail >= need + imminent

    def can_ever_serve(self, n_tokens: int) -> bool:
        """Whether a request resident at `n_tokens` could ever fit an
        otherwise-empty pool (needs its pages all at once)."""
        return n_tokens <= self.max_len and \
            self.pages_for(n_tokens) <= self.num_pages - 1

    # -- slot / page lifecycle ---------------------------------------------
    def alloc(self) -> int:
        if not self._free:
            raise PoolExhausted(
                f"all {self.num_slots} KV slots are in flight")
        return self._free.pop()

    def free(self, slot: int) -> None:
        """Release `slot` and drop one reference on each of its pages —
        shared prefix pages another request (or the cache) still holds
        stay resident; sole-owner pages return to the free list."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if self._free.is_free(slot):
            raise ValueError(f"slot {slot} is already free")
        for i in range(int(self._pages_held[slot])):
            self.release_page(int(self.page_table[slot, i]))
        self.page_table[slot] = 0       # dead writes land in junk page 0
        self._pages_held[slot] = 0
        self.lengths[slot] = 0
        self._free.push(slot)
        if self.prefix_cache is not None:
            # this free may have turned shared pages into cache-only ones;
            # keep the cache inside its LRU pin budget
            self.prefix_cache.enforce_budget()

    def release_page(self, page: int) -> None:
        """Drop one reference on `page`; free it at refcount zero.  A
        cache-pinned page whose last request-reference just left becomes
        reclaimable (the cache's own reference keeps it resident)."""
        self.page_refs[page] -= 1
        if self.page_refs[page] == 0:
            self._free_pages.push(page)
        elif self.page_refs[page] < 0:
            raise ValueError(f"page {page} released below zero references")
        elif self.page_refs[page] == 1 and self.page_cached[page]:
            self._cache_only += 1

    def pin_page(self, page: int) -> None:
        """The prefix cache takes its reference on `page` (cell insert);
        the inserting request still holds it, so it is shared, not
        cache-only."""
        self.page_refs[page] += 1
        self.page_cached[page] = True

    def unpin_page(self, page: int) -> None:
        """The prefix cache drops its reference on `page` (cell evict)."""
        if self.page_refs[page] == 1:
            self._cache_only -= 1
        self.page_cached[page] = False
        self.release_page(page)

    def adopt_run(self, slot: int, pages) -> None:
        """Install a shared page run as the head of `slot`'s page table
        (prefix-cache hit), taking one reference per page.  The slot must
        hold nothing yet; ``reserve_prefix`` then extends it with the
        cold suffix's own pages."""
        if self._pages_held[slot]:
            raise ValueError(
                f"slot {slot} already holds {self._pages_held[slot]} pages; "
                f"a shared run must be adopted first")
        for i, page in enumerate(pages):
            if self.page_refs[page] == 1 and self.page_cached[page]:
                self._cache_only -= 1   # cache-only -> shared again
            self.page_refs[page] += 1
            self.page_table[slot, i] = page
        self._pages_held[slot] = len(pages)

    def _grow(self, slot: int) -> bool:
        """Append one page to `slot`; False when the pool is starved.
        A starved free list reclaims LRU cache-only prefix pages first —
        the cache layer gives way before any request is preempted."""
        held = int(self._pages_held[slot])
        if held >= self.max_pages:
            raise PoolExhausted(
                f"slot {slot} already holds max_pages={self.max_pages}")
        if not self._free_pages and self.prefix_cache is not None:
            self.prefix_cache.reclaim(1)
        if not self._free_pages:
            return False
        page = self._free_pages.pop()
        self.page_refs[page] = 1
        self.page_cached[page] = False
        self.page_table[slot, held] = page
        self._pages_held[slot] = held + 1
        return True

    # -- cache plumbing ----------------------------------------------------
    def insert(self, slot: int, prefill_cache: dict) -> None:
        """Allocate pages for a (batch=1) prefill cache and scatter it in.

        Legacy/test path — it costs one extra copy of the prompt's KV:
        the whole-prompt ``(1, s)`` cache is materialized by the prefill
        step and then re-scattered through the page table.  The serving
        engine now writes through ``reserve_prefix`` + the chunked
        prefill step, which scatters each chunk's KV to its final
        page/offset directly."""
        stores = {n: c for n, c in prefill_cache.items() if n != "index"}
        s = next(iter(stores.values())).shape[2]
        if s > self.max_len:
            raise ValueError(f"prefill length {s} > pool max_len {self.max_len}")
        self.reserve_prefix(slot, s)
        self.cache = _scatter_insert(
            self.cache, jnp.int32(slot),
            jnp.asarray(self.page_table[slot]), stores)
        self.lengths[slot] = s

    def reserve_prefix(self, slot: int, n_tokens: int) -> None:
        """Grow `slot` to hold an `n_tokens` prompt before chunked prefill
        writes into it (all pages up front — the same reservation point
        blocking admission used, so admission order is unchanged)."""
        if n_tokens > self.max_len:
            raise ValueError(
                f"prefix of {n_tokens} tokens > pool max_len {self.max_len}")
        need = self.pages_for(n_tokens)
        if need - int(self._pages_held[slot]) > \
                self.free_pages + self.reclaimable_pages:
            raise PoolExhausted(
                f"prefix of {n_tokens} tokens needs {need} pages, "
                f"{self.free_pages} free")
        for _ in range(need - int(self._pages_held[slot])):
            self._grow(slot)

    def chunk_extras(self, slot: int) -> tuple:
        """The slot's page-table row — the chunk step scatters through it."""
        return (jnp.asarray(self.page_table[slot]),)

    @property
    def kv_bound_cap(self) -> int:
        """Largest KV prefix a chunk could ever need to read back."""
        return self.max_pages * self.page_size

    def adopt(self, new_cache: dict) -> None:
        """Take ownership of the cache returned by a (donating) chunk
        step; the host length mirror advances via ``set_length``."""
        self.cache = new_cache

    def set_length(self, slot: int, n_tokens: int) -> None:
        self.lengths[slot] = n_tokens

    def prepare_decode(self, active_slots) -> list:
        """Grow every active slot whose next token crosses into a fresh
        page; returns the slots the pool could not serve (page-starved),
        in the deterministic order they were visited."""
        starved = []
        for slot in active_slots:
            if self.lengths[slot] >= self._pages_held[slot] * self.page_size:
                if not self._grow(slot):
                    starved.append(slot)
        return starved

    def decode_extras(self) -> tuple:
        """Extra per-step arguments for the jitted decode step: the page
        table."""
        return (jnp.asarray(self.page_table),)

    def grow_for_burst(self, slot: int, want_tokens: int) -> int:
        """Opportunistically back up to `want_tokens` KV positions past
        `slot`'s current length for a speculative verify burst, using ONLY
        genuinely free pages — never the prefix cache's reclaimable pages
        and never another request's (no preemption): a burst is a
        throughput bonus, not a reservation, so it must not change
        admission or eviction behaviour.  Returns how many positions are
        backed (>= 1 after ``prepare_decode`` granted the mandatory next
        token); verify writes beyond that divert to junk page 0 via the
        attention ok-guard and the scheduler caps acceptance to the
        backed count."""
        target = min(int(self.lengths[slot]) + want_tokens, self.max_len)
        while int(self._pages_held[slot]) * self.page_size < target:
            held = int(self._pages_held[slot])
            if held >= self.max_pages or not self._free_pages:
                break
            page = self._free_pages.pop()
            self.page_refs[page] = 1
            self.page_cached[page] = False
            self.page_table[slot, held] = page
            self._pages_held[slot] = held + 1
        backed = int(self._pages_held[slot]) * self.page_size \
            - int(self.lengths[slot])
        return max(min(backed, want_tokens,
                       self.max_len - int(self.lengths[slot])), 0)

    def sync_index(self) -> None:
        """Re-upload the host length mirror as the device index vector.

        After a verify step the device index is stale by design (the step
        returns it unchanged — acceptance is a host decision), so the
        scheduler calls this once per spec step.  Free slots sync to 0,
        which is harmless: admission re-seeds their index before any
        decode reads it."""
        self.cache = dict(self.cache,
                          index=jnp.asarray(self.lengths, jnp.int32))

    def update(self, new_cache: dict, active_slots=()) -> None:
        """Adopt the cache returned by a (donating) decode step; the length
        mirror advances only for the slots that were active this step."""
        self.cache = new_cache
        for slot in active_slots:
            self.lengths[slot] += 1
