"""Chunked-prefill pipeline: token-identity with blocking prefill (swept
over chunk sizes, page sizes, and preemption resumes), scheduler- and
router-level overlap (a replica mid-prefill keeps serving decode ticks),
the deterministic TTFT step proxy, the long-prompt trace preset, and the
tuner's chunk-size + napkin plumbing."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.serving import (PoolExhausted, ReplicaRouter, Request, Scheduler,
                           ServeEngine, longprompt_trace, zipf_trace)
from repro.serving.prefill import bucket_len
from repro.serving.scheduler import _Entry

ARCH = "deepseek-7b-smoke"
SLOTS, MAX_LEN = 4, 64

_ENGINES: dict = {}


def engine_for(page_size=0, num_pages=0, slots=SLOTS, max_len=MAX_LEN):
    """Engines are expensive (jit); share them across tests by config."""
    key = (page_size, num_pages, slots, max_len)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, num_slots=slots, max_len=max_len, seed=0,
            page_size=page_size, num_pages=num_pages,
            log=lambda *a, **k: None)
    return _ENGINES[key]


def _tokens(stats):
    return [r.tokens for r in sorted(stats.results, key=lambda r: r.rid)]


# ---------------------------------------------------------------------------
# Token identity: chunked == blocking


def test_chunked_matches_blocking_across_chunk_sizes():
    """The keystone: the chunk-prefill step scatters KV to the same final
    positions blocking prefill + insert produced, bitwise, so every chunk
    size decodes the identical stream."""
    e = engine_for()
    reqs = zipf_trace(10, e.cfg.vocab_size, max_prompt=24, max_new=16,
                      seed=3)
    ref = e.run(reqs, prefill_chunk=0)            # blocking baseline
    for chunk in (4, 8, 16, 64):
        got = e.run(reqs, prefill_chunk=chunk)
        assert _tokens(got) == _tokens(ref), f"chunk={chunk}"
    # no preemptions on a roomy pool: every prompt token was
    # ingested through the chunk pipeline exactly once
    chunked = e.run(reqs, prefill_chunk=8)
    assert chunked.prefill_tokens == sum(len(r.prompt) for r in reqs)


def test_chunked_matches_blocking_moe_family():
    """The chunk scan rides the MoE backbone (aux-loss carry) too."""
    e = ServeEngine(arch="granite-moe-3b-a800m-smoke", num_slots=3,
                    max_len=48, seed=0, log=lambda *a, **k: None)
    reqs = zipf_trace(6, e.cfg.vocab_size, max_prompt=16, max_new=10,
                      seed=1)
    assert _tokens(e.run(reqs, prefill_chunk=4)) == \
        _tokens(e.run(reqs, prefill_chunk=0))


@settings(max_examples=6, deadline=None)
@given(chunk=st.sampled_from([4, 8, 16, 32]),
       page_size=st.sampled_from([16, MAX_LEN]),
       trace_seed=st.integers(min_value=0, max_value=30))
def test_chunked_equivalence_sweep(chunk, page_size, trace_seed):
    """Hypothesis sweep: any chunk size x page size (MAX_LEN: one page per
    slot) x mixed-length trace is token-identical to the blocking
    full-prompt prefill."""
    e = engine_for(page_size=page_size)
    reqs = zipf_trace(6, e.cfg.vocab_size, max_prompt=16, max_new=12,
                      seed=trace_seed)
    assert _tokens(e.run(reqs, prefill_chunk=chunk)) == \
        _tokens(e.run(reqs, prefill_chunk=0))


def test_chunked_preemption_resume_equivalent():
    """Page-scarce chunked serving preempts mid-decode and re-ingests
    prompt+generated through the chunk pipeline — the resumed stream must
    match an uninterrupted blocking run exactly."""
    roomy = engine_for()
    scarce = engine_for(page_size=8, num_pages=13)  # 96 KV tokens
    reqs = zipf_trace(12, roomy.cfg.vocab_size, max_prompt=24, max_new=32,
                      seed=3)
    ref = roomy.run(reqs, prefill_chunk=0)
    got = scarce.run(reqs, prefill_chunk=8)
    assert got.preemptions > 0
    assert _tokens(got) == _tokens(ref)
    again = scarce.run(reqs, prefill_chunk=8)
    assert again.preemptions == got.preemptions
    assert _tokens(again) == _tokens(got)


# ---------------------------------------------------------------------------
# Overlap: prompt ingestion no longer stalls decode


def test_scheduler_decodes_while_prompt_mid_prefill():
    """Regression for the admission stall: with a chunked manager, a
    decode tick runs in the same step that ingests a queued prompt's
    chunk — in-flight requests keep streaming."""
    e = engine_for()
    sched = Scheduler(e.make_pool(), e.prefill_fn, e.decode_fn,
                      sampler=e.sampler, chunk_step_fn=e.chunk_fn,
                      prefill_chunk=8)
    rng = np.random.RandomState(0)
    short = Request(rid=0, prompt=rng.randint(1, 100, 4).astype(np.int32),
                    max_new_tokens=32)
    long = Request(rid=1, prompt=rng.randint(1, 100, 48).astype(np.int32),
                   max_new_tokens=4)
    assert sched.try_admit(_Entry(short))
    sched.step()                      # short's one chunk lands -> active
    assert 0 in [a.st.rid for a in sched.active.values()]
    free_before = sched.free_tokens
    assert sched.try_admit(_Entry(long))
    # the queued 48-token backlog is charged against the load signal
    # beyond the slot reservation itself
    assert sched.free_tokens < free_before - len(long.prompt)
    n0 = len(next(iter(sched.active.values())).st.tokens)
    sched.step()
    assert sched.prefill_backlog      # long is mid-prefill (48 > 8) ...
    n1 = len(next(iter(sched.active.values())).st.tokens)
    assert n1 == n0 + 1               # ... and short still decoded a token
    while sched.has_work:
        sched.admit_from_queue()
        sched.step()
    stats = sched.stats()
    assert stats.overlap_steps >= 1
    assert [r.rid for r in stats.results] == [0, 1]


def test_router_overlaps_prefill_with_fleet_decode_and_lowers_ttft():
    """Acceptance: on the long-prompt trace the chunked fleet overlaps
    ingestion with decode (overlap ticks observed) and its mean TTFT step
    proxy is strictly lower than the blocking lockstep loop's — with
    token-identical output."""
    e = engine_for()
    router = ReplicaRouter([e] * 3, policy="least_loaded",
                           log=lambda *a, **k: None)
    reqs = longprompt_trace(9, e.cfg.vocab_size, max_prompt=MAX_LEN,
                            max_new=8, seed=0)
    blocking = router.run(reqs, policy="continuous", prefill_chunk=0)
    chunked = router.run(reqs, policy="continuous", prefill_chunk=8)
    assert _tokens(chunked) == _tokens(blocking)
    assert chunked.overlap_steps > 0
    assert blocking.overlap_steps == 0
    assert chunked.mean_ttft_steps < blocking.mean_ttft_steps
    # deterministic: a replay reproduces the proxy exactly
    again = router.run(reqs, policy="continuous", prefill_chunk=8)
    assert again.mean_ttft_steps == chunked.mean_ttft_steps


def test_single_replica_router_chunked_token_identical_to_engine():
    """N=1 routing stays a no-op under chunked prefill."""
    e = engine_for()
    router = ReplicaRouter([e], policy="least_loaded",
                           log=lambda *a, **k: None)
    reqs = zipf_trace(8, e.cfg.vocab_size, max_prompt=24, max_new=12,
                      seed=7)
    a = router.run(reqs, prefill_chunk=8)
    ref = e.run(reqs, prefill_chunk=8)
    assert _tokens(a) == _tokens(ref)
    assert a.replica_stats[0].decode_steps == ref.decode_steps
    assert a.replica_stats[0].prefill_chunks == ref.prefill_chunks


# ---------------------------------------------------------------------------
# Observability / plumbing


def test_stats_expose_chunk_pipeline_counters():
    e = engine_for()
    reqs = zipf_trace(8, e.cfg.vocab_size, max_prompt=24, max_new=8, seed=5)
    chunked = e.run(reqs, prefill_chunk=8)
    assert chunked.prefill_chunks > len(reqs)     # multi-chunk prompts
    assert chunked.prefill_tokens == sum(len(r.prompt) for r in reqs)
    # compile-cache proxy: (chunk bucket, kv bound) pairs, both pow2 —
    # bounded by log2(chunk) x log2(max_len)
    assert 1 <= chunked.prefill_compiles <= 16
    assert chunked.prefill_queue_peak >= 1
    assert chunked.mean_ttft_steps > 0
    blocking = e.run(reqs, prefill_chunk=0)
    assert blocking.overlap_steps == 0
    assert blocking.prefill_chunks == len(reqs)   # one whole-prompt chunk
    assert _tokens(blocking) == _tokens(chunked)


def test_bucket_len_is_next_power_of_two():
    assert [bucket_len(n) for n in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]


def test_paged_reserve_prefix_and_exhaustion():
    from repro.configs import smoke_config
    from repro.models.transformer import model_for
    from repro.serving import PagedKVCachePool
    pool = PagedKVCachePool(model_for(smoke_config("deepseek-7b"),
                                      remat="none"),
                            num_slots=2, max_len=32, page_size=8,
                            num_pages=4)             # 3 usable pages
    s0 = pool.alloc()
    pool.reserve_prefix(s0, 17)                      # 3 pages
    assert pool.free_pages == 0
    s1 = pool.alloc()
    with pytest.raises(PoolExhausted):
        pool.reserve_prefix(s1, 8)
    pool.free(s0)
    pool.reserve_prefix(s1, 8)                       # now it fits
    with pytest.raises(ValueError, match="max_len"):
        pool.reserve_prefix(s1, 33)


def test_longprompt_trace_deterministic_and_long():
    a = longprompt_trace(16, 1000, max_prompt=128, max_new=8, seed=4)
    b = longprompt_trace(16, 1000, max_prompt=128, max_new=8, seed=4)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    lens = [len(r.prompt) for r in a]
    assert all(length <= 128 for length in lens)
    # prefill-stall regime: most prompts sit at the top bucket
    assert sum(length == 128 for length in lens) >= len(lens) // 2
    assert np.mean(lens) >= 64


def test_tuner_picks_chunk_size_and_quotes_ttft():
    from repro.configs.base import ShapeConfig, get_config
    from repro.core.plan import DeploymentPlan
    from repro.core.target import get_target
    from repro.core.tuning import tune

    cfg = get_config(ARCH)
    plan = tune(cfg, ShapeConfig("d", 128, 8, "decode"),
                get_target("local:cpu"))
    chunk = plan.serve_prefill_chunk
    assert chunk >= 8 and (chunk & (chunk - 1)) == 0    # pow2, bucketed
    assert chunk <= 128
    assert plan.napkin["serve_prefill_chunk"] == chunk
    assert "ttft_estimate" in plan.napkin
    again = DeploymentPlan.from_json(plan.to_json())
    assert again.serve_prefill_chunk == chunk
