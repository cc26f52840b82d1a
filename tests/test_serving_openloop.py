"""Open-loop serving: arrival presets (Poisson/bursty virtual-step
stamps), open == closed stream identity, SLO-aware reject admission,
autoscale conservation under drain, the vstep-only regression gate, and
the trace-repetitiveness off-by-one regression (constant-run prompts
read as 0.0 repetitive, so the tuner never turned spec decoding on)."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "benchmarks"))

from repro.core.tuning import SPEC_MAX_K, spec_k_for, ttft_napkin_steps
from repro.serving import (AutoscalePolicy, ReplicaRouter, Request,
                           RequestResult, ServeEngine, bursty_arrivals,
                           percentile_steps, poisson_arrivals,
                           trace_repetitiveness, with_arrivals, zipf_trace)

ARCH = "deepseek-7b-smoke"
SLOTS, MAX_LEN = 3, 64

_ENGINES: dict = {}


def engine_for(slots=SLOTS):
    key = slots
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, target="local:cpu", num_slots=slots, max_len=MAX_LEN,
            seed=0, log=lambda *a, **k: None)
    return _ENGINES[key]


def router_for(engines, **kw):
    return ReplicaRouter(engines, log=lambda *a, **k: None, **kw)


def _trace(n, engine, seed=0):
    return zipf_trace(n, engine.cfg.vocab_size, max_prompt=24, max_new=6,
                      alpha=1.3, seed=seed)


def _tokens(stats):
    return {r.rid: r.tokens for r in stats.results}


# ---------------------------------------------------------------------------
# Satellite: trace_repetitiveness off-by-one regression


def _buggy_repetitiveness(requests, max_n=3):
    """The pre-fix scan: ``range(i - max_n)`` drops the window ending at
    i-1, the only earlier occurrence a period-1 (constant-run) cycle
    ever has."""
    hits = total = 0
    for req in requests:
        p = [int(t) for t in np.asarray(req.prompt)]
        for i in range(max_n, len(p)):
            gram = p[i - max_n + 1:i + 1]
            found = any(p[j:j + max_n] == gram for j in range(i - max_n))
            hits += bool(found)
            total += 1
    return hits / total if total else 0.0


def test_repetitiveness_constant_run_regression():
    # a constant prompt of length max_n+1 is the minimal repetitive
    # input: the gram ending at the last position recurs exactly once
    # earlier, in the window ending at i-1 (j = i - max_n) — the one
    # start index the buggy ``range(i - max_n)`` scan excluded.  The
    # buggy scan therefore saw NO repetition at all and the tuner left
    # speculative decoding off on a maximally predictable trace.
    req = Request(rid=0, prompt=np.full(4, 7, dtype=np.int32),
                  max_new_tokens=4)
    r_fixed = trace_repetitiveness([req])
    r_buggy = _buggy_repetitiveness([req])
    assert r_fixed == 1.0
    assert r_buggy == 0.0
    # ...and the off-by-one flips the tuner's decision end to end:
    # spec_k_for goes from "spec off" to the full draft length
    assert spec_k_for(r_buggy) == 0
    assert spec_k_for(r_fixed) == SPEC_MAX_K
    # longer constant runs: the buggy scan recovers later positions but
    # still undercounts vs the fixed scan (which saturates at 1.0)
    long = Request(rid=1, prompt=np.full(12, 7, dtype=np.int32),
                   max_new_tokens=4)
    assert trace_repetitiveness([long]) == 1.0
    assert _buggy_repetitiveness([long]) < 1.0


def test_repetitiveness_unrelated_prompt_unaffected():
    # a strictly increasing prompt has no repeated n-gram under either
    # scan — the fix must not inflate genuinely novel prompts
    req = Request(rid=0, prompt=np.arange(16, dtype=np.int32),
                  max_new_tokens=4)
    assert trace_repetitiveness([req]) == 0.0
    assert _buggy_repetitiveness([req]) == 0.0


# ---------------------------------------------------------------------------
# Arrival presets


def test_poisson_arrivals_deterministic_and_monotone():
    e = engine_for()
    a = poisson_arrivals(_trace(8, e), mean_gap=4.0, seed=5)
    b = poisson_arrivals(_trace(8, e), mean_gap=4.0, seed=5)
    stamps = [r.arrival_vstep for r in a]
    assert stamps == [r.arrival_vstep for r in b]     # seeded == replayable
    assert stamps == sorted(stamps)                   # cumulative gaps
    assert all(s >= 0 for s in stamps)
    assert stamps[-1] > 0                             # actually spread out
    c = poisson_arrivals(_trace(8, e), mean_gap=4.0, seed=6)
    assert [r.arrival_vstep for r in c] != stamps     # seed moves the draw


def test_bursty_arrivals_modulate_and_validate():
    e = engine_for()
    # a short period relative to the arrival density, so the schedule
    # traverses whole burst/trough cycles within the trace
    reqs = bursty_arrivals(_trace(24, e), mean_gap=6.0, burst=4.0,
                           period=6.0, seed=1)
    stamps = [r.arrival_vstep for r in reqs]
    assert stamps == sorted(stamps)
    gaps = np.diff(stamps)
    assert gaps.max() >= 4 * max(gaps.min(), 1)       # peaks AND troughs
    with pytest.raises(ValueError, match="burst"):
        bursty_arrivals(_trace(2, e), burst=0.5)


def test_with_arrivals_dispatch():
    e = engine_for()
    reqs = with_arrivals(_trace(4, e), "poisson", mean_gap=3.0, seed=2)
    assert any(r.arrival_vstep > 0 for r in reqs)
    reqs = with_arrivals(reqs, "closed")              # closed re-zeroes
    assert all(r.arrival_vstep == 0 for r in reqs)
    with pytest.raises(ValueError, match="arrival mode"):
        with_arrivals(reqs, "uniform")


def test_negative_arrival_rejected():
    e = engine_for()
    reqs = _trace(1, e)
    reqs[0].arrival_vstep = -3
    with pytest.raises(ValueError, match="arrival_vstep"):
        e.run(reqs)


# ---------------------------------------------------------------------------
# SLO bookkeeping primitives


def test_meets_slo_vstep_semantics():
    r = RequestResult(rid=0, prompt_len=4, max_new_tokens=4,
                      v_submit=10, v_first=14, v_done=20)
    assert r.ttft_steps == 4 and r.e2e_steps == 10
    assert r.meets_slo(0, 0)                  # 0 = deadline unset, passes
    assert r.meets_slo(4, 10)
    assert not r.meets_slo(3, 0)              # ttft deadline busted
    assert not r.meets_slo(0, 9)              # e2e deadline busted
    unfinished = RequestResult(rid=1, prompt_len=4, max_new_tokens=4)
    assert not unfinished.meets_slo(100, 100)  # never completed never counts


def test_percentile_steps_empty_is_nan():
    assert np.isnan(percentile_steps([], 99))
    assert percentile_steps([4.0], 99) == 4.0


def test_ttft_napkin_steps():
    # waited + backlog share + own prefill chunk-equivalents
    assert ttft_napkin_steps(64, 16) == 4
    assert ttft_napkin_steps(1, 16) == 1          # ceil, never 0
    assert ttft_napkin_steps(64, 16, backlog_chunks=3, waited_steps=2) == 9


# ---------------------------------------------------------------------------
# Open loop == closed loop (streams), determinism, SLO admission


def test_open_loop_streams_match_closed_replay():
    e = engine_for()
    open_reqs = with_arrivals(_trace(6, e), "poisson", mean_gap=5.0, seed=3)
    closed = [dataclasses.replace(r, arrival_vstep=0) for r in open_reqs]
    s_open = e.run(open_reqs)
    s_closed = e.run(closed)
    assert _tokens(s_open) == _tokens(s_closed)
    # arrivals only ever push latency up, never tokens around
    assert s_open.decode_steps >= s_closed.decode_steps or \
        s_open.generated_tokens == s_closed.generated_tokens


def test_router_open_loop_determinism_with_slo_and_autoscale():
    e = engine_for()
    policy = AutoscalePolicy(min_replicas=1, max_replicas=3)
    kw = dict(policy="continuous", slo_ttft_steps=20, slo_e2e_steps=120,
              admission="reject", autoscale=policy)
    runs = []
    for _ in range(2):
        reqs = with_arrivals(_trace(10, e), "poisson", mean_gap=4.0, seed=7)
        runs.append(router_for([e, e, e]).run(reqs, **kw))
    a, b = runs
    assert [r.arrival_vstep
            for r in with_arrivals(_trace(10, e), "poisson", mean_gap=4.0,
                                   seed=7)] == \
        [r.arrival_vstep
         for r in with_arrivals(_trace(10, e), "poisson", mean_gap=4.0,
                                seed=7)]
    assert a.replica_of == b.replica_of
    assert [(r.rid, r.reason) for r in a.rejected] == \
        [(r.rid, r.reason) for r in b.rejected]
    assert _tokens(a) == _tokens(b)
    for f in ("p50_ttft_steps", "p99_ttft_steps", "p50_e2e_steps",
              "p99_e2e_steps", "goodput_tokens", "total_vsteps"):
        av, bv = getattr(a, f), getattr(b, f)
        assert av == bv or (av != av and bv != bv)  # NaN-safe equality


def test_slo_reject_partitions_and_explains():
    e = engine_for()
    # a brutal 3-vstep TTFT deadline on bunched arrivals: the queue tail
    # provably cannot make it and must be shed with a reason, up front
    reqs = with_arrivals(_trace(9, e), "poisson", mean_gap=1.0, seed=2)
    stats = router_for([e, e]).run(reqs, slo_ttft_steps=3,
                                   admission="reject")
    done = {r.rid for r in stats.results}
    shed = {r.rid for r in stats.rejected}
    assert shed                                     # something was shed
    assert done | shed == set(range(9))             # every rid accounted
    assert not done & shed                          # exactly once each
    for rej in stats.rejected:
        assert "slo_ttft" in rej.reason and rej.predicted_ttft_steps > 3
    # queue admission on the same trace completes everything
    full = router_for([e, e]).run(
        with_arrivals(_trace(9, e), "poisson", mean_gap=1.0, seed=2),
        slo_ttft_steps=3, admission="queue")
    assert {r.rid for r in full.results} == set(range(9))
    assert not full.rejected


def test_reject_needs_slo():
    e = engine_for()
    with pytest.raises(ValueError, match="needs"):
        router_for([e, e]).run(_trace(2, e), admission="reject")


# ---------------------------------------------------------------------------
# Autoscaling


def test_autoscale_drain_conserves_requests():
    e = engine_for()
    # bursty load so the fleet grows at the peak, then drains in the
    # trough — and despite replicas entering/leaving the accepting set,
    # every request completes exactly once on exactly one replica
    reqs = with_arrivals(_trace(14, e), "bursty", mean_gap=3.0, seed=4)
    stats = router_for([e, e, e]).run(
        reqs, slo_ttft_steps=12,
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=3,
                                  drain_idle_rounds=4))
    assert sorted(r.rid for r in stats.results) == list(range(14))
    assert sorted(stats.replica_of) == list(range(14))
    assert len({r.rid for r in stats.results}) == 14
    assert stats.autoscale_grows > 0                # the peak forced growth
    assert stats.peak_replicas > 1
    events = [(ev.action, ev.replica) for ev in stats.autoscale_events]
    assert all(a in ("grow", "drain", "stop") for a, _ in events)


def test_autoscale_beats_fixed_on_ttft():
    e = engine_for()
    mk = lambda: with_arrivals(_trace(12, e), "poisson",  # noqa: E731
                               mean_gap=6.0, seed=3)
    fixed = router_for([e]).run(mk(), slo_ttft_steps=20, slo_e2e_steps=120)
    auto = router_for([e, e, e]).run(
        mk(), slo_ttft_steps=20, slo_e2e_steps=120,
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=3))
    assert _tokens(fixed) == _tokens(auto)          # scaling never resamples
    assert auto.p99_ttft_steps < fixed.p99_ttft_steps
    assert auto.goodput_tokens >= fixed.goodput_tokens
    m = auto.to_metrics()
    assert m["router_ttft_p99_steps"] == auto.p99_ttft_steps
    assert m["router_goodput_tokens"] == auto.goodput_tokens
    assert m["router_autoscale_grows"] == auto.autoscale_grows


# ---------------------------------------------------------------------------
# Satellite audit: the CI gate is vstep-only — wall-clock is advisory


def test_regression_gate_ignores_wall_metrics():
    import serving_throughput as bench

    def snap(**over):
        cell = {"tokens_per_s": 50.0, "p99_ttft_steps": 10.0,
                "goodput_tokens": 100, "tokens_per_step": 2.0}
        cell.update(over)
        return {"cells": {"cell": dict(cell)}}

    # a 10x wall-clock collapse alone must NOT trip the gate (advisory)
    bench._check_regression(snap(), snap(tokens_per_s=5.0))
    # ...but the vstep-derived SLO metrics are enforced
    with pytest.raises(SystemExit, match="p99 TTFT"):
        bench._check_regression(snap(), snap(p99_ttft_steps=20.0))
    with pytest.raises(SystemExit, match="goodput"):
        bench._check_regression(snap(), snap(goodput_tokens=10))
    with pytest.raises(SystemExit, match="tokens/step"):
        bench._check_regression(snap(), snap(tokens_per_step=1.0))
    # an idle fleet's NaN percentile serializes to null: skips the gate
    # in either position instead of tripping it
    bench._check_regression(snap(p99_ttft_steps=None),
                            snap(p99_ttft_steps=None))
    bench._check_regression(snap(p99_ttft_steps=None),
                            snap(p99_ttft_steps=3.0))
