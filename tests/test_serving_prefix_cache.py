"""Shared-prefix KV page cache: refcount lifecycle (a preempted sharer
must never free pages another request still references), LRU eviction
under page pressure (never while refcount > 1), suffix-only prefill
accounting, and the equivalence bar — cached and cold runs emit
bit-identical token streams across chunk sizes x shared-prefix depths x
preemption — plus the prefix-key unification and the serving-bench gate
fixes that ride along."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
sys.path.insert(0, str(Path(__file__).parent.parent / "benchmarks"))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.serving import (PagedKVCachePool, PrefixCache, ReplicaRouter,
                           ServeEngine, prefix_key, prefix_replica,
                           sharedprefix_trace)

ARCH = "deepseek-7b-smoke"

_ENGINES: dict = {}
_MODELS: dict = {}


def engine_for(page_size=16, num_pages=0, slots=6, max_len=64):
    """Engines are expensive (jit); share them across tests by config."""
    key = (page_size, num_pages, slots, max_len)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, num_slots=slots, max_len=max_len, seed=0,
            page_size=page_size, num_pages=num_pages,
            log=lambda *a, **k: None)
    return _ENGINES[key]


def model():
    if "m" not in _MODELS:
        from repro.configs import smoke_config
        from repro.models.transformer import model_for
        _MODELS["m"] = model_for(smoke_config("deepseek-7b"), remat="none")
    return _MODELS["m"]


def _tokens(stats):
    return [r.tokens for r in sorted(stats.results, key=lambda r: r.rid)]


def _prompt(n, start=1):
    return np.arange(start, start + n, dtype=np.int32)


def _brute_reclaimable(cache):
    """O(cells) ground truth for the pool's O(1) cache-only counter."""
    refs = cache.pool.page_refs
    return sum(1 for c in cache._cells.values() if refs[c.page] == 1)


# ---------------------------------------------------------------------------
# Refcount lifecycle on the pool


def test_refcounted_insert_free_attach_reclaim():
    """The full life of a shared run: insert pins prompt-covered pages,
    a request freeing its slot only decrements, a hit re-attaches by
    pointer copy, and reclaim returns sole-cache pages to the free
    list."""
    pool = PagedKVCachePool(model(), num_slots=3, max_len=64,
                            page_size=8, num_pages=16)
    cache = PrefixCache(pool, max_pages=8)
    prompt = _prompt(20)                       # 3 pages, 2 fully covered
    s0 = pool.alloc()
    pool.reserve_prefix(s0, len(prompt))
    run = [int(pool.page_table[s0, i]) for i in range(2)]
    partial = int(pool.page_table[s0, 2])
    assert cache.insert(prompt, s0) == 2       # the partial page is mutable
    assert [int(pool.page_refs[p]) for p in run] == [2, 2]
    assert int(pool.page_refs[partial]) == 1

    free_before = pool.free_pages
    pool.free(s0)                              # request done
    assert pool.free_pages == free_before + 1  # only the partial page freed
    assert [int(pool.page_refs[p]) for p in run] == [1, 1]
    assert cache.reclaimable_pages == _brute_reclaimable(cache) == 2

    hit = cache.probe(prompt)
    assert hit.n_tokens == 16 and hit.pages == run and hit.pinned == 2
    s1 = pool.alloc()
    assert cache.attach(s1, prompt) == 16      # pointer copies, no KV writes
    pool.reserve_prefix(s1, len(prompt))
    assert [int(pool.page_table[s1, i]) for i in range(2)] == run
    assert [int(pool.page_refs[p]) for p in run] == [2, 2]
    assert cache.reclaimable_pages == _brute_reclaimable(cache) == 0

    pool.free(s1)                              # a sharer freeing never
    assert [int(pool.page_refs[p]) for p in run] == [1, 1]   # frees the run
    assert cache.reclaimable_pages == _brute_reclaimable(cache) == 2
    assert cache.reclaim(2) == 2
    assert cache.reclaimable_pages == _brute_reclaimable(cache) == 0
    assert [int(pool.page_refs[p]) for p in run] == [0, 0]
    assert cache.probe(prompt).n_tokens == 0
    assert cache.hits == 1 and cache.misses == 0 and cache.tokens_saved == 16


def test_preempted_sharer_leaves_other_requests_pages_alone():
    """Two sharers of one run: evicting (freeing) one must leave the
    run resident and readable for the other — the bug class refcounts
    exist to kill."""
    pool = PagedKVCachePool(model(), num_slots=3, max_len=64,
                            page_size=8, num_pages=16)
    cache = PrefixCache(pool)
    prompt_a = np.concatenate([_prompt(16), _prompt(5, start=100)])
    prompt_b = np.concatenate([_prompt(16), _prompt(7, start=200)])
    s0 = pool.alloc()
    pool.reserve_prefix(s0, len(prompt_a))
    cache.insert(prompt_a, s0)
    run = [int(pool.page_table[s0, i]) for i in range(2)]

    s1 = pool.alloc()
    cache.attach(s1, prompt_b)                 # shares both head pages
    pool.reserve_prefix(s1, len(prompt_b))
    assert [int(pool.page_refs[p]) for p in run] == [3, 3]

    pool.free(s1)                              # "preempted" sharer
    assert [int(pool.page_refs[p]) for p in run] == [2, 2]
    assert [int(pool.page_table[s0, i]) for i in range(2)] == run
    assert not pool._free_pages.is_free(run[0])
    assert not pool._free_pages.is_free(run[1])
    # reclaim refuses while the survivor still references the run
    assert cache.reclaim(2) == 0
    # and releasing an already-free page is caught, not silently negative
    pool.free(s0)
    cache.reclaim(2)
    assert [int(pool.page_refs[p]) for p in run] == [0, 0]
    with pytest.raises(ValueError, match="below zero"):
        pool.release_page(run[0])


def test_lru_eviction_order_and_shared_page_protection():
    """Reclaim takes the least-recently-used cells first, deepest page
    first within a chain, and never a cell whose page a live request
    still shares."""
    pool = PagedKVCachePool(model(), num_slots=4, max_len=64,
                            page_size=8, num_pages=20)
    cache = PrefixCache(pool)
    pa = np.concatenate([_prompt(16), _prompt(1, start=500)])
    pb = np.concatenate([_prompt(16, start=300), _prompt(1, start=600)])

    sa = pool.alloc()
    pool.reserve_prefix(sa, len(pa))
    cache.insert(pa, sa)
    run_a = [int(pool.page_table[sa, i]) for i in range(2)]
    pool.free(sa)
    sb = pool.alloc()
    pool.reserve_prefix(sb, len(pb))
    cache.insert(pb, sb)
    run_b = [int(pool.page_table[sb, i]) for i in range(2)]
    pool.free(sb)

    # touching A (an attach) makes B the LRU chain
    sc = pool.alloc()
    cache.attach(sc, pa)
    pool.reserve_prefix(sc, len(pa))
    pool.free(sc)
    assert cache.reclaim(2) == 2
    assert cache.probe(pb).n_tokens == 0       # B evicted ...
    assert cache.probe(pa).n_tokens == 16      # ... A survives

    # a live sharer pins A outright: nothing left to reclaim
    sd = pool.alloc()
    cache.attach(sd, pa)
    pool.reserve_prefix(sd, len(pa))
    assert cache.reclaimable_pages == 0
    assert cache.reclaim(4) == 0
    assert cache.probe(pa).pages == run_a
    assert run_a != run_b


def test_insert_respects_pin_budget():
    """max_pages caps cache-only pages: over-budget inserts evict LRU
    cells back under the tuner's pin quota."""
    pool = PagedKVCachePool(model(), num_slots=4, max_len=64,
                            page_size=8, num_pages=24)
    cache = PrefixCache(pool, max_pages=2)
    for i, start in enumerate((1, 300, 700)):
        p = np.concatenate([_prompt(16, start=start), _prompt(1, start=900)])
        s = pool.alloc()
        pool.reserve_prefix(s, len(p))
        cache.insert(p, s)
        pool.free(s)
    assert cache.reclaimable_pages <= 2
    assert cache.evictions >= 2


def test_pool_reclaims_cache_before_admission_fails():
    """Page pressure evicts cache cells before anything starves: a pool
    whose free list is exhausted but whose cache holds reclaimable pages
    still admits (only the cold suffix's pages are new)."""
    pool = PagedKVCachePool(model(), num_slots=2, max_len=32,
                            page_size=8, num_pages=4)   # 3 usable pages
    cache = PrefixCache(pool)
    pa = _prompt(17)                           # 3 pages, 2 cached
    s0 = pool.alloc()
    pool.reserve_prefix(s0, len(pa))
    cache.insert(pa, s0)
    pool.free(s0)
    assert pool.free_pages == 1                # 2 pinned by the cache
    assert pool.free_tokens == 3 * 8           # reclaimable counts as free
    pb = _prompt(17, start=400)                # no hit, needs all 3 pages
    assert pool.can_admit(len(pb), hit=cache.probe(pb))
    s1 = pool.alloc()
    pool.reserve_prefix(s1, len(pb))           # grows via LRU reclaim
    assert pool._pages_held[s1] == 3
    assert cache.probe(pa).n_tokens == 0       # cache gave way


def test_admission_reserves_only_cold_suffix():
    """With a full-run hit, can_admit asks for the suffix's pages alone —
    and does not double-count the hit's cache-only pages as spendable."""
    pool = PagedKVCachePool(model(), num_slots=2, max_len=32,
                            page_size=8, num_pages=4)   # 3 usable pages
    cache = PrefixCache(pool)
    pa = _prompt(17)
    s0 = pool.alloc()
    pool.reserve_prefix(s0, len(pa))
    cache.insert(pa, s0)
    pool.free(s0)                              # 1 free + 2 cache-pinned
    hit = cache.probe(pa)
    assert hit.pinned == 2
    # 3 pages total, 2 shared -> 1 cold page needed, 1 genuinely free
    assert pool.can_admit(len(pa), hit=hit)
    s1 = pool.alloc()
    assert cache.attach(s1, pa) == 16
    pool.reserve_prefix(s1, len(pa))
    assert pool.free_pages == 0
    # the same ask WITHOUT the hit would need 3 pages from 1 free + 0
    # reclaimable (the run is now shared, not reclaimable)
    assert not pool.can_admit(len(pa), hit=None)


# ---------------------------------------------------------------------------
# Equivalence: cached == cold, bit-identical


def test_cached_matches_cold_and_saves_prefill():
    e = engine_for()
    reqs = sharedprefix_trace(12, e.cfg.vocab_size, seed=0)
    cold = e.run(reqs, prefix_cache=False)
    hot = e.run(reqs, prefix_cache=True)
    assert _tokens(hot) == _tokens(cold)
    assert hot.prefix_hits > 0
    assert hot.prefill_tokens_saved > 0
    assert hot.prefill_tokens + hot.prefill_tokens_saved == \
        cold.prefill_tokens
    assert hot.prefill_chunks < cold.prefill_chunks
    # deterministic: fresh pool + fresh cache per run replays exactly
    again = e.run(reqs, prefix_cache=True)
    assert _tokens(again) == _tokens(hot)
    assert again.prefix_hits == hot.prefix_hits
    assert again.prefill_tokens_saved == hot.prefill_tokens_saved


@settings(max_examples=8, deadline=None)
@given(chunk=st.sampled_from([0, 4, 8, 16]),
       head_len=st.sampled_from([16, 32]),
       trace_seed=st.integers(min_value=0, max_value=25))
def test_cached_equivalence_sweep(chunk, head_len, trace_seed):
    """Hypothesis sweep: any chunk size (0 = blocking) x shared-prefix
    depth x trace is token-identical with the cache on or off."""
    e = engine_for()
    reqs = sharedprefix_trace(8, e.cfg.vocab_size, head_len=head_len,
                              seed=trace_seed)
    cold = e.run(reqs, prefill_chunk=chunk, prefix_cache=False)
    hot = e.run(reqs, prefill_chunk=chunk, prefix_cache=True)
    assert _tokens(hot) == _tokens(cold)


def test_preemption_with_sharing_matches_roomy_reference():
    """Page scarcity + sharing: preempting a sharer mid-flight must not
    corrupt the run other requests read — the resumed streams match a
    roomy cache-off reference bit for bit."""
    roomy = engine_for()
    scarce = engine_for(page_size=8, num_pages=13)     # 96 KV tokens
    reqs = sharedprefix_trace(10, roomy.cfg.vocab_size, head_len=16,
                              max_new=24, seed=3)
    ref = roomy.run(reqs, prefix_cache=False)
    got = scarce.run(reqs, prefill_chunk=8, prefix_cache=True)
    assert got.preemptions > 0
    assert _tokens(got) == _tokens(ref)
    again = scarce.run(reqs, prefill_chunk=8, prefix_cache=True)
    assert again.preemptions == got.preemptions
    assert _tokens(again) == _tokens(got)


def test_router_per_replica_caches_compose():
    """prefix_affinity colocates sharers, so per-replica caches hit
    without any cross-replica coordination — and the fleet's streams
    stay identical to the cache-off fleet."""
    e = engine_for()
    router = ReplicaRouter([e] * 3, policy="prefix_affinity",
                           log=lambda *a, **k: None)
    # more requests than the fleet holds at once: hits need a wave that
    # arrives after an earlier sharer's prefill completed (there is no
    # in-flight dedup — concurrent misses both pay, deterministically)
    reqs = sharedprefix_trace(30, e.cfg.vocab_size, seed=5)
    cold = router.run(reqs)
    hot = router.run(reqs, prefix_cache=True)
    assert _tokens(hot) == _tokens(cold)
    assert hot.prefill_tokens_saved > 0
    assert hot.prefill_tokens + hot.prefill_tokens_saved == \
        cold.prefill_tokens


# ---------------------------------------------------------------------------
# Satellites: prefix-key unification, imbalance NaN, bench gates


def test_prefix_key_is_the_single_source():
    prompt = _prompt(20, start=5)
    assert prefix_key(prompt, 8) == \
        np.asarray(prompt, np.int32)[:8].tobytes()
    assert prefix_key(prompt) == np.asarray(prompt, np.int32).tobytes()
    # shorter-than-ask prompts key on what exists (numpy slice semantics)
    assert prefix_key(prompt[:3], 8) == \
        np.asarray(prompt[:3], np.int32).tobytes()
    # routing still consumes the same bytes deterministically
    assert prefix_replica(prompt, 3) == prefix_replica(prompt.copy(), 3)


def test_imbalance_nan_when_fleet_saw_no_traffic():
    from repro.serving import RouterStats
    from repro.serving.scheduler import ServeStats

    def zero():
        return ServeStats(results=[], wall_s=0.0, decode_steps=0,
                          generated_tokens=0, occupancy=0.0)
    rs = RouterStats(results=[], replica_stats=[zero(), zero()],
                     replica_of={}, wall_s=0.0)
    assert rs.imbalance != rs.imbalance        # NaN, not a fake 1.0
    busy = zero()
    busy.peak_resident_tokens = 8
    rs2 = RouterStats(results=[], replica_stats=[busy, zero()],
                      replica_of={}, wall_s=0.0)
    assert rs2.imbalance == 2.0
    # the benchmark emitter maps NaN to JSON null, not a bare NaN token
    from serving_throughput import _num
    assert _num(rs.imbalance) is None
    assert _num(1.23456) == 1.2346


def test_check_regression_guards_each_metric_independently():
    """A baseline cell predating tokens_per_step must still enforce the
    TTFT ceiling (the old `continue` skipped everything)."""
    from serving_throughput import _check_regression
    base = {"cells": {"c": {"tokens_per_s": 10.0, "mean_ttft_steps": 10.0}}}
    fresh = {"cells": {"c": {"tokens_per_s": 10.0, "tokens_per_step": 1.0,
                             "mean_ttft_steps": 20.0}}}
    with pytest.raises(SystemExit, match="TTFT"):
        _check_regression(base, fresh)
    ok = {"cells": {"c": {"tokens_per_s": 10.0, "tokens_per_step": 1.0,
                          "mean_ttft_steps": 10.0}}}
    _check_regression(base, ok)                # no tokens_per_step gate yet
    # and a dead prefix cache fails wherever the baseline had savings
    base2 = {"cells": {"c": {"tokens_per_s": 1.0,
                             "prefill_tokens_saved": 50}}}
    fresh2 = {"cells": {"c": {"tokens_per_s": 1.0,
                              "prefill_tokens_saved": 0}}}
    with pytest.raises(SystemExit, match="reuse went dead"):
        _check_regression(base2, fresh2)


def test_check_regression_fails_on_ungated_new_cells():
    from serving_throughput import _check_regression
    base = {"cells": {"a": {"tokens_per_s": 1.0}}}
    fresh = {"cells": {"a": {"tokens_per_s": 1.0},
                       "b": {"tokens_per_s": 1.0},
                       "c": {"tokens_per_s": 1.0}}}
    with pytest.raises(SystemExit, match="2 new cell.*refresh"):
        _check_regression(base, fresh)
    # removed cells still fail (coverage regression)
    with pytest.raises(SystemExit, match="missing"):
        _check_regression(fresh, base)


# ---------------------------------------------------------------------------
# Trace + tuner plumbing


def test_sharedprefix_trace_clusters_heads():
    a = sharedprefix_trace(16, 1000, seed=2)
    b = sharedprefix_trace(16, 1000, seed=2)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    heads = [tuple(r.prompt[:32]) for r in a]
    assert len(set(heads)) <= 4
    # Zipf clustering: the most popular head dominates
    top = max(set(heads), key=heads.count)
    assert heads.count(top) >= len(a) // 2
    assert all(len(r.prompt) > 32 for r in a)  # >= 1 private suffix token


def test_tuner_carves_prefix_cache_budget():
    from repro.configs.base import ShapeConfig, get_config
    from repro.core.plan import DeploymentPlan
    from repro.core.target import get_target
    from repro.core.tuning import tune

    cfg = get_config(ARCH)
    plan = tune(cfg, ShapeConfig("d", 128, 8, "decode"),
                get_target("local:cpu"))
    assert 0 < plan.serve_prefix_cache_pages < plan.serve_num_pages
    assert "serve_prefix_cache" in plan.napkin
    again = DeploymentPlan.from_json(plan.to_json())
    assert again.serve_prefix_cache_pages == plan.serve_prefix_cache_pages
    assert "serve prefix" in plan.report()
