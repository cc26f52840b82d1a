"""Draft-then-verify speculative decoding: the keystone bit-identity
property (speculative streams == sequential streams, over draft length x
page size x trace x sampling style x preemption pressure), burst page
charging (accepted bursts spend only genuinely free pages; overflow
verify writes land in junk page 0, never a refcounted shared page), the
n-gram drafter, the tuner's spec_k pick, and the top_k/top_p request
validation that rides this PR."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.configs import smoke_config
from repro.core.tuning import SPEC_MAX_K, SPEC_MIN_REPETITIVENESS, spec_k_for
from repro.models.params import init_params
from repro.models.transformer import model_for
from repro.serving import (K_CAP, NGramDrafter, PagedKVCachePool,
                           ReplicaRouter, Request, ServeEngine,
                           effective_top_k, repetitive_trace,
                           trace_repetitiveness, uniform_trace, zipf_trace)
from repro.training.steps import build_verify_step_slots

ARCH = "deepseek-7b-smoke"
SPEC_ARCH = "picolm-4-smoke"
SLOTS, MAX_LEN = 4, 64

_ENGINES: dict = {}
_BASELINES: dict = {}


def engine_for(arch=ARCH, page_size=16, num_pages=0, slots=SLOTS,
               max_len=MAX_LEN):
    """Engines are expensive (jit); share them across tests by config."""
    key = (arch, page_size, num_pages, slots, max_len)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=arch, target="local:cpu", num_slots=slots, max_len=max_len,
            seed=0, page_size=page_size, num_pages=num_pages,
            log=lambda *a, **k: None)
    return _ENGINES[key]


def _tokens(stats):
    return [r.tokens for r in sorted(stats.results, key=lambda r: r.rid)]


def _trace(kind, engine, sampled, n=8, max_new=12):
    kw = dict(seed=3, max_new=max_new)
    if sampled:
        kw.update(temperature=0.8, top_k=8, top_p=0.9)
    vocab = engine.cfg.vocab_size
    if kind == "zipf":
        return zipf_trace(n, vocab, max_prompt=24, **kw)
    return repetitive_trace(n, vocab, prompt_len=8, **kw)


# ---------------------------------------------------------------------------
# keystone: speculative streams are bit-identical to sequential decode


@settings(max_examples=8, deadline=None)
@given(k=st.sampled_from([1, 2, 4]),
       page_size=st.sampled_from([16, MAX_LEN]),
       kind=st.sampled_from(["zipf", "repetitive"]),
       sampled=st.booleans(),
       tight=st.booleans())
def test_spec_streams_bit_identical(k, page_size, kind, sampled, tight):
    """spec_k in {1,2,4} x page size (MAX_LEN: one page per slot) x trace
    x greedy/sampled x page pressure: every combination must reproduce
    the spec-off streams exactly.  `tight` shrinks the page pool so
    preemption and re-prefill resume interleave with verify bursts."""
    if tight:
        engine = engine_for(page_size=8, num_pages=12)
    else:
        engine = engine_for(page_size=page_size)
    reqs = _trace(kind, engine, sampled)
    base_key = (id(engine), kind, sampled)
    if base_key not in _BASELINES:
        _BASELINES[base_key] = _tokens(engine.run(reqs, spec_k=0))
    spec = engine.run(reqs, spec_k=k)
    assert _tokens(spec) == _BASELINES[base_key]
    assert spec.spec_verify_steps > 0
    assert spec.spec_drafted_tokens == spec.spec_verify_steps * k
    assert 0 <= spec.spec_accepted_tokens <= spec.spec_drafted_tokens


def test_spec_accepts_bursts_on_repetitive_smallvocab():
    """On the 4-token-vocab probe arch the greedy continuation is n-gram
    predictable: the drafter must clear >1 accepted-tokens/verify-step
    and finish in strictly fewer scheduler ticks, with identical output."""
    engine = engine_for(arch=SPEC_ARCH)
    reqs = repetitive_trace(8, engine.cfg.vocab_size, max_new=32, seed=0)
    base = engine.run(reqs, spec_k=0)
    spec = engine.run(reqs, spec_k=4)
    assert _tokens(spec) == _tokens(base)
    assert spec.accepted_per_verify > 1.0
    assert spec.decode_steps < base.decode_steps


def test_spec_identical_under_preemption_pressure():
    """A page pool too small for the working set: preemptions and
    re-prefill resumes must interleave with verify bursts without
    perturbing the streams."""
    engine = engine_for(arch=SPEC_ARCH, page_size=8, num_pages=10,
                        max_len=64)
    reqs = repetitive_trace(8, engine.cfg.vocab_size, max_new=24, seed=1)
    base = engine.run(reqs, spec_k=0)
    spec = engine.run(reqs, spec_k=4)
    assert spec.preemptions > 0          # the pressure actually happened
    assert _tokens(spec) == _tokens(base)


def test_spec_through_router_fleet():
    """An N=2 fleet with spec on is token-identical to the spec-off
    fleet, and RouterStats aggregates the replica counters."""
    e_on = ServeEngine(arch=SPEC_ARCH, target="local:cpu", num_slots=2,
                       max_len=MAX_LEN, seed=0, spec_k=4,
                       log=lambda *a, **k: None)
    reqs = repetitive_trace(6, e_on.cfg.vocab_size, max_new=16, seed=2)
    r_on = ReplicaRouter([e_on] * 2, log=lambda *a, **k: None).run(reqs)
    e_off = engine_for(arch=SPEC_ARCH, slots=2)
    r_off = ReplicaRouter([e_off] * 2, log=lambda *a, **k: None).run(reqs)
    assert _tokens(r_on) == _tokens(r_off)
    assert r_on.spec_verify_steps == \
        sum(s.spec_verify_steps for s in r_on.replica_stats) > 0
    assert r_on.accepted_per_verify > 1.0
    assert r_off.spec_verify_steps == 0


# ---------------------------------------------------------------------------
# burst page charging: junk page 0, never a refcounted page


def _model():
    return model_for(smoke_config("deepseek-7b"), remat="none")


def _prefill_cache(model, params, n):
    toks = jnp.ones((1, n), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks}, None)
    return cache


def test_grow_for_burst_spends_only_free_pages():
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8,
                            num_pages=6)              # pages 1..5 usable
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 8))  # page-exact: 1 page
    assert pool.free_pages == 4
    # a 5-token burst wants 2 pages; both are free -> fully backed
    assert pool.grow_for_burst(s0, 5) == 5
    assert pool._pages_held[s0] == 2 and pool.free_pages == 3
    # a second ask is already covered by the held pages (idempotent)
    assert pool.grow_for_burst(s0, 5) == 5
    assert pool.free_pages == 3
    # mid-page: the burst straddles into one fresh page
    pool.lengths[s0] = 15
    assert pool.grow_for_burst(s0, 5) == 5
    assert pool._pages_held[s0] == 3 and pool.free_pages == 2
    # near max_len the backing is clamped to the slot's headroom
    pool.lengths[s0] = 30
    assert pool.grow_for_burst(s0, 10) == 2
    assert pool._pages_held[s0] == 4 and pool.free_pages == 1


def test_grow_for_burst_never_reclaims_cached_pages():
    """An empty free list with reclaimable prefix-cache pages: the decode
    path's _grow would reclaim them, but a burst is a bonus, not a
    reservation — grow_for_burst must leave the cache intact."""
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8,
                            num_pages=3)              # pages 1, 2 usable
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 8))  # page 1
    s1 = pool.alloc()
    pool.insert(s1, _prefill_cache(model, params, 8))  # page 2
    page0 = int(pool.page_table[s0, 0])
    pool.pin_page(page0)         # a prefix cache takes its reference
    pool.free(s0)                # ... and becomes the page's sole owner
    assert pool.free_pages == 0 and pool.reclaimable_pages == 1
    assert pool.grow_for_burst(s1, 4) == 0    # nothing genuinely free
    assert pool.page_refs[page0] == 1 and pool.page_cached[page0]
    assert pool.reclaimable_pages == 1        # cache untouched


def test_verify_overflow_writes_divert_to_junk_page():
    """A slot at exact page capacity with nothing free: the verify step's
    burst positions have no backing page, so their KV writes must land in
    reserved junk page 0 — and a refcounted page SHARED with another
    request must come through bit-identical."""
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8,
                            num_pages=2)              # page 1 only
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 8))  # fills page 1 exactly
    shared = int(pool.page_table[s0, 0])
    s1 = pool.alloc()
    pool.adopt_run(s1, [shared])                       # refcounted sharer
    pool.set_length(s1, 8)
    pool.sync_index()
    assert pool.page_refs[shared] == 2
    assert pool.grow_for_burst(s0, 4) == 0             # nothing to back
    before_shared = np.asarray(pool.cache["k"][:, shared])
    before_junk = np.asarray(pool.cache["k"][:, 0])
    verify = build_verify_step_slots(model)
    logits, new_cache = verify(
        params, pool.cache, jnp.ones((2, 4), jnp.int32),
        jnp.ones((2,), jnp.int32), jnp.asarray(pool.page_table))
    pool.adopt(new_cache)
    assert logits.shape[:2] == (2, 4)
    after_shared = np.asarray(pool.cache["k"][:, shared])
    after_junk = np.asarray(pool.cache["k"][:, 0])
    # positions 8..11 have page-table entry 0 -> every write diverted
    assert np.array_equal(before_shared, after_shared)
    assert not np.array_equal(before_junk, after_junk)
    # index stays host-authoritative: the verify step must not advance it
    assert list(np.asarray(pool.cache["index"])) == [8, 8]


# ---------------------------------------------------------------------------
# drafter


def test_ngram_drafter_locks_onto_cycles():
    d = NGramDrafter()
    # period-3 cycle: the longest-suffix rule continues it exactly
    assert d.draft([1, 2, 3, 1, 2, 3, 1, 2], 4) == [3, 1, 2, 3]
    # no recurring suffix: fall back to repeating the last token
    assert d.draft([5, 6, 7], 2) == [7, 7]
    assert d.draft([], 3) == [0, 0, 0]
    # proposals extend the working history (a continuation, not k
    # independent guesses): the drafted cycle keeps rolling
    assert d.draft([4, 9, 4, 9], 5) == [4, 9, 4, 9, 4]
    with pytest.raises(ValueError):
        NGramDrafter(max_n=0)


def test_trace_repetitiveness_separates_regimes():
    rep = repetitive_trace(16, 4, seed=0)
    rand = uniform_trace(16, 256, seed=0)
    r_hi, r_lo = trace_repetitiveness(rep), trace_repetitiveness(rand)
    assert r_hi > SPEC_MIN_REPETITIVENESS
    assert r_lo < SPEC_MIN_REPETITIVENESS
    assert r_hi > r_lo


# ---------------------------------------------------------------------------
# tuner pick


def test_spec_k_for_thresholds():
    assert spec_k_for(0.0) == 0
    assert spec_k_for(SPEC_MIN_REPETITIVENESS - 0.01) == 0
    k_mid, k_hi = spec_k_for(0.5), spec_k_for(0.95)
    assert 1 <= k_mid <= k_hi <= SPEC_MAX_K
    assert spec_k_for(1.0) == SPEC_MAX_K      # clamped, saturating


def test_tuner_wires_repetitiveness_into_plan():
    from repro.core.appspec import AppSpec
    from repro.core.build import BuildService
    from repro.core.target import get_target
    plans = {}
    for rep in (0.0, 0.9):
        app = AppSpec(arch=ARCH, shape="decode_32k",
                      shape_overrides={"seq_len": MAX_LEN, "global_batch": 4,
                                       "serve_repetitiveness": rep},
                      run="serve --engine continuous")
        plans[rep] = BuildService().build(app, get_target("local:cpu"),
                                          lower=False).plan
    assert plans[0.0].serve_spec_k == 0
    assert plans[0.9].serve_spec_k == spec_k_for(0.9) > 0
    assert "serve_spec" in plans[0.9].napkin
    # spec_k=None defers the ENGINE to the plan's pick
    eng = ServeEngine(arch=SPEC_ARCH, target="local:cpu", num_slots=2,
                      max_len=MAX_LEN, seed=0, spec_k=None,
                      repetitiveness=0.9, log=lambda *a, **k: None)
    assert eng.spec_k == spec_k_for(0.9)


# ---------------------------------------------------------------------------
# top_k validation + effective-k surfacing (satellite regression)


def test_top_k_above_cap_rejected_at_submission():
    engine = engine_for()
    bad = [Request(rid=0, prompt=np.ones(4, np.int32),
                   max_new_tokens=4, temperature=0.8, top_k=K_CAP + 1)]
    with pytest.raises(ValueError, match="top_k"):
        engine.run(bad)
    router = ReplicaRouter([engine], log=lambda *a, **k: None)
    with pytest.raises(ValueError, match="top_k"):
        router.run(bad)


def test_effective_top_k_surfaced_in_stats():
    # ask for K_CAP on a 4-token vocab: valid, but the sampler can only
    # ever keep 4 — ServeStats must surface the k actually applied
    assert effective_top_k(K_CAP, 4) == 4
    assert effective_top_k(8, 256) == 8
    assert effective_top_k(0, 256) == 0
    engine = engine_for(arch=SPEC_ARCH)
    reqs = repetitive_trace(2, engine.cfg.vocab_size, max_new=4, seed=0,
                            temperature=0.7, top_k=K_CAP)
    stats = engine.run(reqs)
    assert stats.effective_top_k == {0: 4, 1: 4}
    greedy = engine.run(repetitive_trace(2, engine.cfg.vocab_size,
                                         max_new=4, seed=0))
    assert greedy.effective_top_k == {}      # top_k off -> nothing to report


# ---------------------------------------------------------------------------
# top_p (nucleus) sampling


def test_top_p_validation():
    engine = engine_for()
    for bad_p in (0.0, -0.5, 1.5):
        bad = [Request(rid=0, prompt=np.ones(4, np.int32), max_new_tokens=4,
                       temperature=0.8, top_p=bad_p)]
        with pytest.raises(ValueError, match="top_p"):
            engine.run(bad)
        with pytest.raises(ValueError, match="top_p"):
            ReplicaRouter([engine], log=lambda *a, **k: None).run(bad)


def test_top_p_one_is_bitwise_passthrough():
    engine = engine_for()
    base = engine.run(zipf_trace(6, engine.cfg.vocab_size, max_prompt=16,
                                 max_new=8, seed=5, temperature=0.9,
                                 top_k=8))
    explicit = engine.run(zipf_trace(6, engine.cfg.vocab_size, max_prompt=16,
                                     max_new=8, seed=5, temperature=0.9,
                                     top_k=8, top_p=1.0))
    assert _tokens(base) == _tokens(explicit)


def test_top_p_filters_and_stays_deterministic_across_layouts():
    kw = dict(max_prompt=16, max_new=10, seed=7, temperature=1.5, top_p=0.5)
    e_cont = engine_for(page_size=MAX_LEN)     # one page per slot
    e_paged = engine_for()
    nucleus = e_cont.run(zipf_trace(8, e_cont.cfg.vocab_size, **kw))
    # the filter really bites: some stream must differ from top_p=1.0
    full = e_cont.run(zipf_trace(8, e_cont.cfg.vocab_size,
                                 **{**kw, "top_p": 1.0}))
    assert _tokens(nucleus) != _tokens(full)
    # same draw on a repeat run and across page sizes
    again = e_cont.run(zipf_trace(8, e_cont.cfg.vocab_size, **kw))
    paged = e_paged.run(zipf_trace(8, e_paged.cfg.vocab_size, **kw))
    assert _tokens(nucleus) == _tokens(again) == _tokens(paged)
