"""Paged KV cache: page allocator lifecycle, fragmentation/exhaustion,
preemption-and-resume determinism, per-request sampling, and the keystone
equivalence — paged and contiguous layouts produce token-identical output
on the same mixed-length traces, while paged admits strictly more
concurrent requests under the same tuner HBM budget."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.configs import smoke_config
from repro.models.params import init_params
from repro.models.transformer import model_for
from repro.serving import (KVCachePool, PagedKVCachePool, PoolExhausted,
                           Request, ServeEngine, zipf_trace)

ARCH = "deepseek-7b-smoke"
SLOTS, MAX_LEN = 4, 64

_ENGINES: dict = {}


def engine_for(layout, page_size=0, num_pages=0, slots=SLOTS,
               max_len=MAX_LEN, target="local:cpu", kv_kernel="auto"):
    """Engines are expensive (jit); share them across tests by config."""
    key = (layout, page_size, num_pages, slots, max_len, target, kv_kernel)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, target=target, num_slots=slots, max_len=max_len,
            seed=0, kv_layout=layout, page_size=page_size,
            num_pages=num_pages, kv_kernel=kv_kernel,
            log=lambda *a, **k: None)
    return _ENGINES[key]


def _model():
    return model_for(smoke_config("deepseek-7b"), remat="none")


def _prefill_cache(model, params, n):
    toks = jnp.ones((1, n), jnp.int32)
    _, cache = model.prefill(params, {"tokens": toks}, None)
    return cache


def _tokens(stats):
    return [r.tokens for r in sorted(stats.results, key=lambda r: r.rid)]


# ---------------------------------------------------------------------------
# PagedKVCachePool allocator


def test_paged_pool_page_accounting_and_lifo_reuse():
    pool = PagedKVCachePool(_model(), num_slots=3, max_len=32, page_size=8,
                            num_pages=9)          # 8 usable, page 0 junk
    assert pool.max_pages == 4 and pool.free_pages == 8
    model, params = _model(), None
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 12))   # 2 pages
    assert pool.free_pages == 6
    first_pages = list(pool.page_table[s0, :2])
    assert 0 not in first_pages                   # junk page never issued
    s1 = pool.alloc()
    pool.insert(s1, _prefill_cache(model, params, 5))    # 1 page
    assert pool.free_pages == 5
    pool.free(s0)
    assert pool.free_pages == 7
    assert list(pool.page_table[s0]) == [0, 0, 0, 0]     # row zeroed
    # freed pages are the next reissued (deterministic LIFO)
    s2 = pool.alloc()
    pool.insert(s2, _prefill_cache(model, params, 16))   # 2 pages
    assert set(pool.page_table[s2, :2]) == set(first_pages)


def test_paged_pool_grows_on_demand_and_starves():
    pool = PagedKVCachePool(_model(), num_slots=2, max_len=32, page_size=8,
                            num_pages=4)          # 3 usable pages
    params = init_params(_model().param_table(), jax.random.PRNGKey(0))
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(_model(), params, 8))  # fills page exactly
    assert pool.free_pages == 2
    # next token crosses a page boundary -> on-demand growth
    assert pool.prepare_decode([s0]) == []
    assert pool._pages_held[s0] == 2 and pool.free_pages == 1
    # mid-page: no growth
    pool.lengths[s0] = 9
    assert pool.prepare_decode([s0]) == []
    assert pool.free_pages == 1
    # drain the pool -> the next boundary crossing starves
    pool.lengths[s0] = 16
    assert pool.prepare_decode([s0]) == []
    pool.lengths[s0] = 24
    assert pool.prepare_decode([s0]) == [s0]


def test_paged_pool_exhaustion_and_errors():
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8,
                            num_pages=3)          # 2 usable pages
    s0, s1 = pool.alloc(), pool.alloc()
    with pytest.raises(PoolExhausted, match="slots"):
        pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 16))   # takes both pages
    with pytest.raises(PoolExhausted, match="pages"):
        pool.insert(s1, _prefill_cache(model, params, 8))
    with pytest.raises(ValueError, match="max_len"):
        pool.insert(s1, _prefill_cache(model, params, 33))
    # free-mask error paths: same errors as the contiguous pool, O(1) now
    pool.free(s0)
    with pytest.raises(ValueError, match="already free"):
        pool.free(s0)
    with pytest.raises(ValueError, match="out of range"):
        pool.free(99)


def test_contiguous_pool_free_mask_same_errors():
    pool = KVCachePool(_model(), num_slots=2, max_len=8)
    s = pool.alloc()
    pool.free(s)
    with pytest.raises(ValueError, match="already free"):
        pool.free(s)
    with pytest.raises(ValueError, match="out of range"):
        pool.free(99)
    assert pool.alloc() == s            # LIFO reissue preserved


def test_paged_insert_scatters_through_page_table():
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8)
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 12))
    k = np.asarray(pool.cache["k"], np.float32)
    p0, p1 = pool.page_table[s0, 0], pool.page_table[s0, 1]
    assert np.abs(k[:, p0]).sum() > 0                  # page fully written
    assert np.abs(k[:, p1, :4]).sum() > 0              # second page half
    assert np.abs(k[:, p1, 4:]).sum() == 0
    assert np.abs(k[:, 0]).sum() == 0                  # junk page untouched
    unallocated = [p for p in range(pool.num_pages) if p not in (0, p0, p1)]
    assert np.abs(k[:, unallocated]).sum() == 0


# ---------------------------------------------------------------------------
# Engine equivalence: paged == contiguous, token-identical


def test_paged_matches_contiguous_on_mixed_length_trace():
    ec = engine_for("contiguous")
    ep = engine_for("paged", page_size=16)
    reqs = zipf_trace(12, ec.cfg.vocab_size, max_prompt=24, max_new=32,
                      seed=3)
    a = ec.run(reqs, policy="continuous")
    b = ep.run(reqs, policy="continuous")
    assert _tokens(a) == _tokens(b)
    assert a.generated_tokens == b.generated_tokens
    # and under gang scheduling too
    sa = ec.run(reqs, policy="static")
    sb = ep.run(reqs, policy="static")
    assert _tokens(sa) == _tokens(sb) == _tokens(a)


def test_paged_matches_contiguous_moe_family():
    """The page table rides the MoE backbone's scan (aux-loss carry) too."""
    ec = ServeEngine(arch="granite-moe-3b-a800m-smoke", num_slots=3,
                     max_len=48, seed=0, log=lambda *a, **k: None)
    ep = ServeEngine(arch="granite-moe-3b-a800m-smoke", num_slots=3,
                     max_len=48, seed=0, kv_layout="paged", page_size=8,
                     log=lambda *a, **k: None)
    reqs = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=10,
                      seed=1)
    assert _tokens(ec.run(reqs)) == _tokens(ep.run(reqs))


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m-smoke",
                                  "dbrx-132b-smoke"])
def test_moe_serving_matches_capacity_dispatch_where_none_drop(
        arch, monkeypatch):
    """Dropless serving gives the token stream the capacity dispatch
    (``moe_mlp``, the training path) gives where its capacity drops no
    token: the same layer, computed another way.  In float32, as the two
    round a bfloat16 sum in different orders (a near tie then flips)."""
    from repro.configs import base
    from repro.models import moe

    def capacity_dispatch(p, x, cfg, valid=None):
        roomy = cfg.replace(
            capacity_factor=cfg.num_experts / cfg.experts_per_token)
        y, _ = moe.moe_mlp(p, x, roomy, None)
        return y, jnp.zeros((cfg.num_experts,), jnp.int32)

    f32 = smoke_config(arch).replace(name=arch + "-f32",
                                     param_dtype=jnp.float32,
                                     activation_dtype=jnp.float32)
    monkeypatch.setitem(base.ARCHS, f32.name, {
        "full": f32, "smoke": f32, "skip_shapes": (), "is_smoke": True})
    kw = dict(arch=f32.name, num_slots=3, max_len=48, seed=0,
              kv_layout="paged", page_size=8, log=lambda *a, **k: None)
    reqs = zipf_trace(6, smoke_config(arch).vocab_size, max_prompt=16,
                      max_new=10, seed=1)
    dropless = _tokens(ServeEngine(**kw).run(reqs, prefill_chunk=4))
    monkeypatch.setattr(moe, "held_experts_mlp", capacity_dispatch)
    assert _tokens(ServeEngine(**kw).run(reqs, prefill_chunk=4)) == dropless


@settings(max_examples=5, deadline=None)
@given(page_size=st.sampled_from([8, 16, 32]),
       trace_seed=st.integers(min_value=0, max_value=30))
def test_paged_equivalence_sweep(page_size, trace_seed):
    """Hypothesis sweep: for any page size and mixed-length trace, the two
    memory layouts decode token-identical streams."""
    ec = engine_for("contiguous")
    ep = engine_for("paged", page_size=page_size)
    reqs = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=12,
                      seed=trace_seed)
    assert _tokens(ec.run(reqs)) == _tokens(ep.run(reqs))


# ---------------------------------------------------------------------------
# Preemption / starvation


def test_preemption_and_resume_deterministic_and_equivalent():
    """Scarce pages force mid-decode preemptions; resumed requests must
    re-generate exactly the stream an uninterrupted run produces."""
    ec = engine_for("contiguous")
    scarce = engine_for("paged", page_size=8, num_pages=13)  # 96 KV tokens
    reqs = zipf_trace(12, ec.cfg.vocab_size, max_prompt=24, max_new=32,
                      seed=3)
    ref = ec.run(reqs, policy="continuous")
    a = scarce.run(reqs, policy="continuous")
    assert a.preemptions > 0
    assert _tokens(a) == _tokens(ref)
    b = scarce.run(reqs, policy="continuous")
    assert b.preemptions == a.preemptions and b.decode_steps == a.decode_steps
    assert _tokens(b) == _tokens(a)
    assert [r.preemptions for r in a.results] == \
        [r.preemptions for r in b.results]


def test_pool_exhausted_on_page_starvation_mid_decode():
    """A page pool smaller than one request's full length cannot make
    progress: preempt-and-resume would livelock, so the scheduler raises.
    Without an eos the worst case is certain and rejected before any work
    (completed results are never thrown away); with an eos the request is
    admitted optimistically and starves mid-decode."""
    tiny = engine_for("paged", page_size=8, num_pages=3, slots=2)
    reqs = zipf_trace(2, tiny.cfg.vocab_size, max_prompt=24, max_new=40,
                      seed=7)
    with pytest.raises(PoolExhausted):
        tiny.run(reqs)

    hopeful = ServeEngine(arch=ARCH, num_slots=2, max_len=64, seed=0,
                          kv_layout="paged", page_size=8, num_pages=3,
                          eos_id=-1, log=lambda *a, **k: None)
    req = Request(rid=0, prompt=np.arange(1, 17, dtype=np.int32),
                  max_new_tokens=40)
    with pytest.raises(PoolExhausted, match="mid-decode"):
        hopeful.run([req])


def test_oversized_request_rejected_before_any_work_is_discarded():
    """A trace mixing servable requests with one that can never fit must
    fail fast — not after the servable ones already ran."""
    tiny = engine_for("paged", page_size=8, num_pages=3, slots=2)
    good = zipf_trace(3, tiny.cfg.vocab_size, max_prompt=8, max_new=4,
                      seed=0)                     # <= 11 resident tokens
    bad = [Request(rid=9, prompt=np.ones((16,), np.int32),
                   max_new_tokens=40)]            # 55 resident > 16 capacity
    with pytest.raises(PoolExhausted, match="never"):
        tiny.run(good + bad)


def test_top_k_beyond_sampler_cap_rejected():
    from repro.serving.sampling import K_CAP
    ec = engine_for("contiguous")
    bad = zipf_trace(1, ec.cfg.vocab_size, max_prompt=8, max_new=4, seed=0,
                     temperature=1.0, top_k=K_CAP + 1)
    with pytest.raises(ValueError, match="top_k"):
        ec.run(bad)


# ---------------------------------------------------------------------------
# Per-request sampling


def test_sampling_deterministic_and_layout_agnostic():
    ec = engine_for("contiguous")
    ep = engine_for("paged", page_size=16)
    reqs = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=12,
                      seed=5, temperature=0.8, top_k=8)
    s1 = ep.run(reqs)
    s2 = ep.run(reqs)
    assert _tokens(s1) == _tokens(s2)          # deterministic replay
    sc = ec.run(reqs)
    assert _tokens(s1) == _tokens(sc)          # layout-independent draws
    for r in s1.results:
        assert all(0 <= t < ec.cfg.vocab_size for t in r.tokens)


def test_top_k_one_is_greedy_and_temperature_changes_tokens():
    ec = engine_for("contiguous")
    greedy = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=12,
                        seed=5)
    k1 = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=12,
                    seed=5, temperature=2.0, top_k=1)
    hot = zipf_trace(6, ec.cfg.vocab_size, max_prompt=16, max_new=12,
                     seed=5, temperature=1.5)
    g = ec.run(greedy)
    assert _tokens(ec.run(k1)) == _tokens(g)
    assert _tokens(ec.run(hot)) != _tokens(g)


# ---------------------------------------------------------------------------
# Fused Pallas paged-attention kernel (kv_kernel="pallas")


def test_kernel_on_token_identical_to_gather_engine_level():
    """Engine-level keystone for the fused kernel: the SAME trace decoded
    with kv_kernel="pallas" and kv_kernel="gather" yields bit-identical
    token streams under both schedulers (the kernel reproduces the gather
    path's bf16 rounding recipe, not just its math)."""
    ep = engine_for("paged", page_size=16)
    ek = engine_for("paged", page_size=16, kv_kernel="pallas")
    assert ep.kv_kernel == "gather"          # auto resolves via the plan
    assert ek.kv_kernel == "pallas"
    reqs = zipf_trace(6, ep.cfg.vocab_size, max_prompt=16, max_new=10,
                      seed=3)
    a = ep.run(reqs, policy="continuous")
    b = ek.run(reqs, policy="continuous")
    assert _tokens(a) == _tokens(b)
    assert a.decode_steps == b.decode_steps
    # gang scheduling exercises the all-slots-resident shape too
    assert _tokens(ep.run(reqs, policy="static")) == \
        _tokens(ek.run(reqs, policy="static"))


def test_kernel_survives_preemption_and_junk_rows():
    """Scarce pages force mid-decode preemptions: freed slots leave
    zeroed page-table rows (and junk-page writes) that the kernel must
    mask in-kernel.  Token streams still match the gather path exactly."""
    scarce = engine_for("paged", page_size=8, num_pages=13)
    scarce_k = engine_for("paged", page_size=8, num_pages=13,
                          kv_kernel="pallas")
    reqs = zipf_trace(8, scarce.cfg.vocab_size, max_prompt=16, max_new=16,
                      seed=3)
    a = scarce.run(reqs, policy="continuous")
    b = scarce_k.run(reqs, policy="continuous")
    assert _tokens(a) == _tokens(b)
    assert b.preemptions == a.preemptions


def test_kernel_on_token_identical_to_gather_moe_family():
    """The MoE backbone hands its decode layers the same carried pool:
    kernel-on and gather streams match there too."""
    kw = dict(arch="granite-moe-3b-a800m-smoke", num_slots=3, max_len=48,
              seed=0, kv_layout="paged", page_size=8,
              log=lambda *a, **k: None)
    eg = ServeEngine(kv_kernel="gather", **kw)
    ek = ServeEngine(kv_kernel="pallas", **kw)
    reqs = zipf_trace(6, eg.cfg.vocab_size, max_prompt=16, max_new=10,
                      seed=1)
    assert _tokens(eg.run(reqs)) == _tokens(ek.run(reqs))


def test_contiguous_engine_rejects_pallas_kv_kernel():
    with pytest.raises(ValueError, match="kv_kernel"):
        ServeEngine(arch=ARCH, num_slots=2, max_len=32, seed=0,
                    kv_layout="contiguous", kv_kernel="pallas",
                    log=lambda *a, **k: None)


# ---------------------------------------------------------------------------
# Overwrite clamp: a full slot's extra write must land in junk page 0


def test_full_slot_extra_write_routes_to_junk_not_shared_page():
    """Regression for the decode write clamp: a slot already at its
    page-run capacity (idx // page_size == max_pages) used to WRAP its
    write into the slot's last page via jnp.clip — and under the
    shared-prefix cache that page may be refcounted by other live
    requests.  The ok-guard must divert the overflow to the reserved
    junk page 0, leaving every real page bitwise untouched."""
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = PagedKVCachePool(model, num_slots=2, max_len=32, page_size=8,
                            num_pages=9)
    s0 = pool.alloc()
    pool.insert(s0, _prefill_cache(model, params, 32))  # 4 pages: at cap
    assert pool._pages_held[s0] == pool.max_pages
    # simulate a prefix-cache share: slot 1's row references s0's last
    # page, refcounted — exactly the page the old clamp would overwrite
    s1 = pool.alloc()
    last = int(pool.page_table[s0, -1])
    pool.page_table[s1, 0] = last
    pool.page_refs[last] += 1

    from repro.training.steps import build_decode_step_slots_paged
    step = jax.jit(build_decode_step_slots_paged(model))  # non-donating
    cache = dict(pool.cache)
    before_k = np.asarray(cache["k"], np.float32).copy()
    before_v = np.asarray(cache["v"], np.float32).copy()
    tokens = jnp.ones((2, 1), jnp.int32)
    active = jnp.asarray([1, 0], jnp.int32)
    _, new_cache = step(params, cache, tokens, active,
                        jnp.asarray(pool.page_table))
    after_k = np.asarray(new_cache["k"], np.float32)
    after_v = np.asarray(new_cache["v"], np.float32)
    # every real page — the shared refcounted one included — is bitwise
    # unchanged; the overflow write landed in the junk page
    np.testing.assert_array_equal(after_k[:, 1:], before_k[:, 1:])
    np.testing.assert_array_equal(after_v[:, 1:], before_v[:, 1:])
    assert np.abs(after_k[:, 0]).sum() > 0    # the write went somewhere


# ---------------------------------------------------------------------------
# Budget: tuner sizing + admit-more acceptance


def _tight_target():
    """CPU target whose budget affords ~3 contiguous worst-case slots."""
    from repro.core.target import TARGETS, TargetSpec, register
    from repro.core.tuning import param_count_estimate

    name = "test:serve-tight"
    if name not in TARGETS:
        from repro.core.tuning import kv_bytes_per_token
        cfg = smoke_config("deepseek-7b")
        hbm = (2 * param_count_estimate(cfg) +
               3.5 * kv_bytes_per_token(cfg) * MAX_LEN) / 0.85
        register(TargetSpec(
            name=name, chip="cpu", mesh_shape=(1,), mesh_axes=("data",),
            peak_flops=5e10, hbm_bw=2e10, hbm_bytes=hbm, ici_bw=1e9,
            scheduler="local", kernels="reference"))
    return name


def test_paged_admits_more_concurrent_requests_same_budget():
    """Acceptance: same tuner HBM budget, same Zipf trace — the paged
    layout holds strictly more requests in flight than contiguous."""
    tgt = _tight_target()
    ec = engine_for("contiguous", slots=8, target=tgt)
    ep = engine_for("paged", slots=8, target=tgt)
    assert ec.num_slots < 8                      # tuner capped worst-case
    reqs = zipf_trace(16, ec.cfg.vocab_size, max_prompt=32, max_new=32,
                      seed=0)
    a = ec.run(reqs, policy="continuous")
    b = ep.run(reqs, policy="continuous")
    assert b.peak_active > a.peak_active
    assert _tokens(a) == _tokens(b)              # same tokens, more overlap
    # the paged pool spends (at most) the same order of HBM
    cont_bytes = ec.num_slots * ec.max_len
    paged_bytes = ep.num_pages * ep.page_size
    assert paged_bytes <= cont_bytes * 1.25


def test_tuner_sizes_paged_pool_and_reports_delta():
    from repro.configs.base import ShapeConfig, get_config
    from repro.core.plan import DeploymentPlan
    from repro.core.target import get_target
    from repro.core.tuning import tune

    cfg = get_config("deepseek-7b-smoke")
    plan = tune(cfg, ShapeConfig("d", 128, 8, "decode"),
                get_target("local:cpu"))
    assert plan.serve_page_size == 16
    assert plan.serve_num_pages > 1
    for key in ("kv_pages", "page_size", "serve_pool_paged",
                "serve_capacity_delta"):
        assert key in plan.napkin, key
    assert "serve kv pages" in plan.report()
    again = DeploymentPlan.from_json(plan.to_json())
    assert again.serve_page_size == plan.serve_page_size
    assert again.serve_num_pages == plan.serve_num_pages

    # a budget-bound target buys fewer pages than the worst case, and the
    # napkin quotes the paged capacity win over contiguous
    big = ShapeConfig("d", 32768, 4096, "decode")
    plan_big = tune(get_config("deepseek-7b"), big, get_target("local:cpu"))
    worst = 4096 * (32768 // 16) + 1
    assert plan_big.serve_num_pages < worst
    assert "serve_capacity_delta" in plan_big.napkin


# ---------------------------------------------------------------------------
# The layer scan writes the carried pool in place: one (layer, page, offset)
# per new token, nothing else


def _random_pool(model, num_slots=3, max_len=32, page_size=8):
    pool = PagedKVCachePool(model, num_slots=num_slots, max_len=max_len,
                            page_size=page_size)
    shape = pool.cache["k"].shape
    kk, kv = jax.random.split(jax.random.PRNGKey(5))
    pool.cache = dict(
        pool.cache,
        k=jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16),
        v=jax.random.normal(kv, shape, jnp.float32).astype(jnp.bfloat16))
    return pool


def _changed_only_at(before, after, written):
    """Every (layer, page, offset) outside `written` (a set of (page,
    offset), for every layer) is bit-identical; each written one moved."""
    keep = np.ones(before.shape[:3], bool)
    for page, off in written:
        keep[:, page, off] = False
        assert not np.array_equal(before[:, page, off], after[:, page, off])
    np.testing.assert_array_equal(before[keep], after[keep])


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["gather", "kernel"])
def test_paged_decode_step_writes_only_the_new_tokens(use_kernel):
    """One decode step over a pool full of data: each active slot's new
    K/V lands at (every layer, its page, its offset) — the first token of
    a fresh page included — an inactive slot's dead write goes to the
    junk page 0, and every other position is bit-identical to before."""
    from repro.training.steps import build_decode_step_slots_paged
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = _random_pool(model)
    lengths = {pool.alloc(): 12, pool.alloc(): 8}    # 8: a fresh page
    idle = pool.alloc()
    for slot, n in lengths.items():
        pool.reserve_prefix(slot, n + 1)
        pool.set_length(slot, n)
    index = jnp.asarray([lengths.get(s, 0) for s in range(3)], jnp.int32)
    cache = dict(pool.cache, index=index)
    step = jax.jit(build_decode_step_slots_paged(model,
                                                 use_kernel=use_kernel))
    active = jnp.asarray([s != idle for s in range(3)], jnp.int32)
    _, new = step(params, cache, jnp.ones((3, 1), jnp.int32), active,
                  jnp.asarray(pool.page_table))
    written = {(int(pool.page_table[s, n // 8]), n % 8)
               for s, n in lengths.items()}
    for name in ("k", "v"):
        before = np.asarray(cache[name], np.float32)
        after = np.asarray(new[name], np.float32)
        after_live = after.copy()
        after_live[:, 0] = before[:, 0]           # the junk page may change
        _changed_only_at(before, after_live, written)
    np.testing.assert_array_equal(np.asarray(new["index"]),
                                  np.asarray(index) + np.asarray(active))


def test_paged_chunk_step_writes_only_the_chunk():
    """One chunk step (16 bucketed tokens, 12 valid, at offset 8 of a slot
    holding 20): the chunk's K/V land at its positions' (page, offset) in
    every layer and nothing else in the pool moves."""
    from repro.training.steps import build_prefill_chunk_step_paged
    model = _model()
    params = init_params(model.param_table(), jax.random.PRNGKey(0))
    pool = _random_pool(model)
    pool.alloc()
    slot = pool.alloc()
    pool.reserve_prefix(slot, 20)
    cache = dict(pool.cache)
    step = jax.jit(build_prefill_chunk_step_paged(model),
                   static_argnums=(6,))
    toks = jnp.arange(1, 17, dtype=jnp.int32)[None]
    _, new = step(params, cache, toks, jnp.int32(slot), jnp.int32(8),
                  jnp.int32(12), 32, *pool.chunk_extras(slot))
    row = pool.page_table[slot]
    written = {(int(row[j // 8]), j % 8) for j in range(8, 24)}
    for name in ("k", "v"):
        _changed_only_at(np.asarray(cache[name], np.float32),
                         np.asarray(new[name], np.float32), written)
    assert int(new["index"][slot]) == 20
