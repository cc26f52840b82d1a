"""Continuous-batching serving engine: scheduler determinism, the
static/continuous equivalence + efficiency contracts, and the engine's
greedy streams against an independent scalar-index decode reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.transformer import model_for
from repro.serving import (PagedKVCachePool, Request, ServeEngine,
                           uniform_trace, zipf_trace)
from repro.training.steps import build_decode_step, build_prefill_step

ARCH = "deepseek-7b-smoke"


@pytest.fixture(scope="module")
def engine():
    return ServeEngine(arch=ARCH, num_slots=4, max_len=64, seed=0,
                       log=lambda *a, **k: None)


# ---------------------------------------------------------------------------
# The pool


def test_pool_rejects_unservable_family():
    with pytest.raises(NotImplementedError):
        PagedKVCachePool(model_for(smoke_config("xlstm-1.3b")), 2, 8)


# ---------------------------------------------------------------------------
# Scheduler / engine behaviour


def test_scheduler_deterministic_under_seeded_trace(engine):
    reqs = zipf_trace(12, engine.cfg.vocab_size, max_prompt=24, max_new=16,
                      seed=3)
    a = engine.run(reqs, policy="continuous")
    b = engine.run(zipf_trace(12, engine.cfg.vocab_size, max_prompt=24,
                              max_new=16, seed=3), policy="continuous")
    assert a.decode_steps == b.decode_steps
    assert a.generated_tokens == b.generated_tokens
    for ra, rb in zip(a.results, b.results):
        assert ra.rid == rb.rid and ra.slot == rb.slot
        assert ra.tokens == rb.tokens


def test_results_cover_all_requests_and_respect_budget(engine):
    reqs = zipf_trace(10, engine.cfg.vocab_size, max_prompt=32, max_new=8,
                      seed=5)
    stats = engine.run(reqs, policy="continuous")
    assert [r.rid for r in stats.results] == list(range(10))
    for req, res in zip(reqs, stats.results):
        assert 1 <= len(res.tokens) <= req.max_new_tokens
        assert res.prompt_len + len(res.tokens) - 1 <= engine.max_len
        assert res.t_done >= res.t_first >= res.t_submit


def test_prompt_longer_than_pool_rejected(engine):
    bad = [Request(rid=0, prompt=np.ones((engine.max_len + 1,), np.int32),
                   max_new_tokens=4)]
    with pytest.raises(ValueError, match="does not fit"):
        engine.run(bad)


def _scalar_decode_reference(engine, reqs, nnew):
    """Greedy tokens ``(len(reqs), nnew)`` from batched prefill +
    scalar-index decode (launch/serve.py's pre-engine behaviour), cache
    padded to the pool's max_len: no pool, no page table, no slot-wise
    step.  ``reqs`` share one prompt length."""
    model, params = engine.model, engine.params
    prefill = jax.jit(build_prefill_step(model))
    decode = jax.jit(build_decode_step(model), donate_argnums=(1,))
    toks = jnp.asarray(np.stack([r.prompt for r in reqs]))
    logits, cache = prefill(params, {"tokens": toks})
    pad = engine.max_len - cache["k"].shape[2]
    for key in ("k", "v"):
        cache[key] = jnp.pad(cache[key],
                             [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
    t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    ref = [np.asarray(t)]
    for _ in range(nnew - 1):
        logits, cache = decode(params, cache, t)
        t = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        ref.append(np.asarray(t))
    return np.concatenate(ref, axis=1)


def test_continuous_matches_static_path_for_uniform_requests(engine):
    """The keystone equivalence: slot-wise continuous decode must produce
    token-identical output to the original scalar-index static path."""
    n, plen, nnew = 4, 16, 8
    reqs = uniform_trace(n, engine.cfg.vocab_size, prompt_len=plen,
                         max_new=nnew, seed=11)
    cont = engine.run(reqs, policy="continuous")
    got = np.stack([r.tokens for r in cont.results])
    np.testing.assert_array_equal(
        got, _scalar_decode_reference(engine, reqs, nnew))


_REFERENCE_ENGINES: dict = {}


@pytest.mark.parametrize("prefill_chunk", [None, 0],
                         ids=["chunked", "blocking"])
@pytest.mark.parametrize("arch", ["deepseek-7b-smoke",
                                  "mistral-large-123b-smoke",
                                  "granite-moe-3b-a800m-smoke"],
                         ids=["dense-mha", "dense-gqa", "moe"])
def test_paged_engine_matches_scalar_decode_reference(arch, prefill_chunk):
    """Dense MHA, dense GQA and MoE: the engine's greedy streams through
    the page pool, prompts ingested in chunks or whole, under both
    scheduling policies, are the scalar-index reference's token for
    token."""
    if arch not in _REFERENCE_ENGINES:
        _REFERENCE_ENGINES[arch] = ServeEngine(
            arch=arch, num_slots=4, max_len=64, seed=0, page_size=16,
            log=lambda *a, **k: None)
    engine = _REFERENCE_ENGINES[arch]
    n, plen, nnew = 4, 24, 10
    reqs = uniform_trace(n, engine.cfg.vocab_size, prompt_len=plen,
                         max_new=nnew, seed=5)
    ref = _scalar_decode_reference(engine, reqs, nnew)
    for policy in ("continuous", "static"):
        stats = engine.run(reqs, policy=policy, prefill_chunk=prefill_chunk)
        got = np.stack([r.tokens for r in stats.results])
        np.testing.assert_array_equal(got, ref)


def test_static_policy_same_tokens_fewer_shared_steps(engine):
    """Same trace, both policies: identical per-request tokens (slot content
    is row-independent), and continuous needs strictly fewer decode steps
    on a heavy-tailed trace (deterministic — no wall-clock flakiness)."""
    reqs = zipf_trace(24, engine.cfg.vocab_size, max_prompt=8, max_new=48,
                      seed=3)
    static = engine.run(reqs, policy="static")
    cont = engine.run(reqs, policy="continuous")
    for rs, rc in zip(static.results, cont.results):
        assert rs.tokens == rc.tokens
    assert cont.decode_steps < static.decode_steps
    assert static.decode_steps >= 1.5 * cont.decode_steps
    assert cont.occupancy > static.occupancy


def test_tuner_serve_branch_sizes_pool():
    from repro.configs.base import ShapeConfig, get_config
    from repro.core.target import get_target
    from repro.core.tuning import tune

    cfg = get_config("deepseek-7b-smoke")
    shape = ShapeConfig("d", 128, 8, "decode")
    plan = tune(cfg, shape, get_target("local:cpu"))
    assert plan.serve_max_len == 128
    # the requested batch's worst case fits: 8 slots x 128 tokens of pages
    # plus the junk page
    assert plan.serve_num_pages == 8 * 128 // plan.serve_page_size + 1
    assert "serve_pool_paged" in plan.napkin
    # plan roundtrips with the new fields
    from repro.core.plan import DeploymentPlan
    again = DeploymentPlan.from_json(plan.to_json())
    assert again.serve_max_len == 128
    assert again.serve_num_pages == plan.serve_num_pages

    # a giant request against the full model must be HBM-capped
    big = ShapeConfig("d", 32768, 4096, "decode")
    plan_big = tune(get_config("deepseek-7b"), big, get_target("local:cpu"))
    assert plan_big.serve_num_pages < 4096 * 32768 // 16 + 1


def test_engine_rejects_cacheless_family():
    with pytest.raises(NotImplementedError):
        ServeEngine(arch="xlstm-1.3b-smoke", num_slots=2, max_len=16,
                    log=lambda *a, **k: None)
