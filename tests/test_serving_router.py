"""Multi-replica serving router: policy determinism (round-robin order,
least-loaded free-token choice, prefix-affinity stability under replica
count), overflow re-routing on page starvation, the N=1 == bare-engine
equivalence, the preempt-tie-break-by-rid regression, the fleet
conservation property (every admitted request completes exactly once on
exactly one replica), and the >= 2.5x in-flight acceptance criterion."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.configs import smoke_config
from repro.serving import (PoolExhausted, ReplicaRouter, Request, ServeEngine,
                           prefix_replica, uniform_trace, zipf_trace)

ARCH = "deepseek-7b-smoke"
SLOTS, MAX_LEN = 4, 64

_ENGINES: dict = {}


def engine_for(page_size=0, num_pages=0, slots=SLOTS, max_len=MAX_LEN,
               target="local:cpu", eos_id=None):
    """Engines are expensive (jit); share them across tests by config."""
    key = (page_size, num_pages, slots, max_len, target, eos_id)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, target=target, num_slots=slots, max_len=max_len,
            seed=0, page_size=page_size, num_pages=num_pages,
            eos_id=eos_id, log=lambda *a, **k: None)
    return _ENGINES[key]


def router_for(engines, policy):
    return ReplicaRouter(engines, policy=policy, log=lambda *a, **k: None)


def _tokens(stats):
    return [r.tokens for r in sorted(stats.results, key=lambda r: r.rid)]


def _tight_target():
    """CPU target whose budget holds ~3 worst-case requests' KV."""
    from repro.core.target import TARGETS, TargetSpec, register
    from repro.core.tuning import kv_bytes_per_token, param_count_estimate

    name = "test:router-tight"
    if name not in TARGETS:
        cfg = smoke_config("deepseek-7b")
        hbm = (2 * param_count_estimate(cfg) +
               3.5 * kv_bytes_per_token(cfg) * MAX_LEN) / 0.85
        register(TargetSpec(
            name=name, chip="cpu", mesh_shape=(1,), mesh_axes=("data",),
            peak_flops=5e10, hbm_bw=2e10, hbm_bytes=hbm, ici_bw=1e9,
            scheduler="local", kernels="reference"))
    return name


# ---------------------------------------------------------------------------
# Construction / validation


def test_router_validates_fleet_and_policy():
    e = engine_for()
    with pytest.raises(ValueError, match="at least one"):
        ReplicaRouter([], log=lambda *a, **k: None)
    with pytest.raises(ValueError, match="policy"):
        ReplicaRouter([e], policy="fastest", log=lambda *a, **k: None)
    moe = ServeEngine(arch="granite-moe-3b-a800m-smoke", num_slots=2,
                      max_len=32, seed=0, log=lambda *a, **k: None)
    with pytest.raises(ValueError, match="one architecture"):
        ReplicaRouter([e, moe], log=lambda *a, **k: None)
    # mixed max_len / eos_id would make output depend on the policy's
    # pick (budget clamp and stop condition differ per replica) — rejected
    short = ServeEngine(arch=ARCH, num_slots=2, max_len=32, seed=0,
                        log=lambda *a, **k: None)
    with pytest.raises(ValueError, match="max_len"):
        ReplicaRouter([e, short], log=lambda *a, **k: None)
    with pytest.raises(ValueError, match="eos_id"):
        ReplicaRouter([e, engine_for(slots=2, eos_id=-1)],
                      log=lambda *a, **k: None)


def test_router_rejects_request_no_replica_can_ever_serve():
    e = engine_for()
    router = router_for([e, e], "round_robin")
    too_long = [Request(rid=0, prompt=np.ones((MAX_LEN + 1,), np.int32),
                        max_new_tokens=4)]
    with pytest.raises(ValueError, match="any replica"):
        router.run(too_long)
    scarce = engine_for(page_size=8, num_pages=3, slots=2)
    tiny_fleet = router_for([scarce, scarce], "least_loaded")
    fat = [Request(rid=0, prompt=np.ones((16,), np.int32),
                   max_new_tokens=40)]         # 55 resident > 16 capacity
    with pytest.raises(PoolExhausted, match="no replica"):
        tiny_fleet.run(fat)


# ---------------------------------------------------------------------------
# Policies


def test_round_robin_deterministic_assignment():
    e = engine_for()
    router = router_for([e, e, e], "round_robin")
    reqs = uniform_trace(6, e.cfg.vocab_size, prompt_len=8, max_new=4,
                         seed=2)
    a = router.run(reqs)
    # ring order: rid i lands on replica i mod 3 (ample capacity, nothing
    # skipped), and a replay reproduces the same assignment and tokens
    assert a.replica_of == {i: i % 3 for i in range(6)}
    b = router.run(uniform_trace(6, e.cfg.vocab_size, prompt_len=8,
                                 max_new=4, seed=2))
    assert b.replica_of == a.replica_of
    assert _tokens(a) == _tokens(b)


def test_round_robin_skips_full_replicas():
    small = engine_for(slots=1)               # one slot per replica
    router = router_for([small, small], "round_robin")
    reqs = uniform_trace(4, small.cfg.vocab_size, prompt_len=8, max_new=12,
                         seed=2)
    a = router.run(reqs)
    # both replicas fill immediately; later rids wait for free slots but
    # every request still completes exactly once
    assert sorted(a.replica_of) == [0, 1, 2, 3]
    assert a.peak_in_flight == 2


def test_least_loaded_picks_replica_with_most_free_tokens():
    one = engine_for(slots=1)                  # 4 pages: 64 free tokens
    four = engine_for(slots=4)                 # 16 pages: 256 free tokens
    router = router_for([one, one, four], "least_loaded")
    reqs = uniform_trace(3, one.cfg.vocab_size, prompt_len=8, max_new=4,
                         seed=0)
    a = router.run(reqs)
    # first pick is the roomiest replica (2); once it holds one request
    # (15 pages, 240 free tokens) it still beats the single-slot replicas
    # (64), so everything lands there while slots remain
    assert a.replica_of[0] == 2
    assert all(idx == 2 for idx in a.replica_of.values())


def test_least_loaded_balances_paged_fleet_by_free_pages():
    # two paged replicas with different page pools: the bigger pool wins
    big = engine_for(page_size=8, num_pages=13)   # 96 KV tokens
    small = engine_for(page_size=8, num_pages=5)  # 32 KV tokens
    router = router_for([small, big], "least_loaded")
    reqs = uniform_trace(1, big.cfg.vocab_size, prompt_len=8, max_new=4,
                         seed=1)
    a = router.run(reqs)
    assert a.replica_of[0] == 1


def test_prefix_affinity_stable_under_replica_count():
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 1000, size=(rng.randint(1, 24),))
               for _ in range(40)]
    for p in prompts:
        # deterministic: a property of (prefix, fleet size) only
        assert prefix_replica(p, 3) == prefix_replica(p, 3)
        # rendezvous hashing: growing the fleet N -> N+1 only ever moves
        # a prefix to the NEW replica, never between the survivors
        r3, r4 = prefix_replica(p, 3), prefix_replica(p, 4)
        assert r4 == r3 or r4 == 3
    # prefix-keyed: a shared prefix maps together whatever the suffix is
    base = np.arange(1, 9, dtype=np.int32)
    a = np.concatenate([base, np.full((6,), 101, np.int32)])
    b = np.concatenate([base, np.full((12,), 907, np.int32)])
    assert prefix_replica(a, 5) == prefix_replica(b, 5)


def test_prefix_affinity_routes_shared_prefixes_together():
    e = engine_for()
    router = ReplicaRouter([e, e, e], policy="prefix_affinity",
                           log=lambda *a, **k: None)
    base = np.arange(1, 9, dtype=np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [base, np.full((i + 1,), 50 + i, np.int32)]),
                    max_new_tokens=4)
            for i in range(4)]
    a = router.run(reqs)
    want = prefix_replica(base, 3, prefix_len=router.prefix_len)
    assert all(idx == want for idx in a.replica_of.values())


# ---------------------------------------------------------------------------
# Overflow / re-route


def test_starved_request_reroutes_instead_of_rejecting():
    """A request that solo-starves a scarce paged replica is evicted and
    completes on a roomier replica — with exactly the token stream an
    uninterrupted run on the roomy replica produces."""
    scarce = engine_for(page_size=8, num_pages=3, slots=2,
                        eos_id=-1)            # 16 KV tokens, optimistic
    roomy = engine_for(slots=2, eos_id=-1)    # fleet-uniform eos
    router = router_for([scarce, roomy], "round_robin")
    req = Request(rid=0, prompt=np.arange(1, 17, dtype=np.int32),
                  max_new_tokens=40)
    a = router.run([req])
    assert a.reroutes >= 1
    assert a.replica_of == {0: 1}
    ref = roomy.run([Request(rid=0, prompt=np.arange(1, 17, dtype=np.int32),
                             max_new_tokens=40)])
    assert _tokens(a) == _tokens(ref)
    # the single-engine path still hard-rejects the same request
    with pytest.raises(PoolExhausted, match="mid-decode"):
        scarce.run([Request(rid=0, prompt=np.arange(1, 17, dtype=np.int32),
                            max_new_tokens=40)])


def test_starved_request_with_no_roomier_replica_fails_fast():
    """When every replica's pool is too small for the evicted request's
    remaining generation, the router raises like the bare engine would —
    it must not grind one token per re-prefill bounce on the replica that
    just proved it cannot finish the request (optimistic eos bound)."""
    scarce = engine_for(page_size=8, num_pages=3, slots=2, eos_id=-1)
    router = router_for([scarce], "least_loaded")
    req = Request(rid=0, prompt=np.arange(1, 17, dtype=np.int32),
                  max_new_tokens=40)
    with pytest.raises(PoolExhausted, match="no longer fit"):
        router.run([req])


# ---------------------------------------------------------------------------
# Preempt tie-break regression (scheduler fix riding this PR)


def test_preempt_tie_break_by_rid_not_submission_order():
    """Two requests admitted in the same step that later both starve: the
    victim is the one with the higher request id, however the trace was
    ordered at submission (previously: whichever was inserted later)."""
    eng = engine_for(page_size=8, num_pages=5, slots=3)
    mk = lambda rid: Request(rid=rid,
                             prompt=np.arange(1, 9, dtype=np.int32) + rid,
                             max_new_tokens=20)
    for order in ([0, 1], [1, 0]):
        stats = eng.run([mk(rid) for rid in order])
        by_rid = sorted(stats.results, key=lambda r: r.rid)
        assert stats.preemptions >= 1
        assert by_rid[1].preemptions >= 1     # rid 1 is always the victim
        assert by_rid[0].preemptions == 0     # rid 0 never preempted


# ---------------------------------------------------------------------------
# Equivalence + acceptance


def test_single_replica_router_token_identical_to_bare_engine():
    """N=1 routing must be a no-op: token-identical output, same decode
    step count, same per-request preemption history."""
    e = engine_for()
    router = router_for([e], "least_loaded")
    reqs = zipf_trace(12, e.cfg.vocab_size, max_prompt=24, max_new=16,
                      seed=3)
    a = router.run(reqs)
    ref = e.run(reqs)
    assert _tokens(a) == _tokens(ref)
    assert [r.rid for r in a.results] == [r.rid for r in ref.results]
    assert a.replica_stats[0].decode_steps == ref.decode_steps
    assert [r.preemptions for r in a.results] == \
        [r.preemptions for r in ref.results]
    assert a.replica_of == {r.rid: 0 for r in ref.results}


def test_mixed_layout_fleet_token_identical_to_single_engine():
    """Routing across three replicas of different page sizes and pools
    (one scarce enough to preempt) never changes tokens."""
    e = engine_for()
    scarce = engine_for(page_size=8, num_pages=13)  # 96 KV tokens
    router = router_for([scarce, e, e], "least_loaded")
    reqs = zipf_trace(10, e.cfg.vocab_size, max_prompt=16, max_new=12,
                      seed=5)
    a = router.run(reqs)
    assert _tokens(a) == _tokens(e.run(reqs))
    assert len(a.replica_of) == 10
    assert len(set(a.replica_of.values())) == 3


def test_least_loaded_3_replicas_sustains_2_5x_in_flight():
    """Acceptance: on the tight-budget Zipf trace, a least_loaded router
    over 3 replicas holds >= 2.5x the in-flight requests of one
    three-slot engine — with token-identical output."""
    tgt = _tight_target()
    single = engine_for(slots=3, target=tgt)
    assert single.num_slots == 3
    router = router_for([single] * 3, "least_loaded")
    reqs = zipf_trace(18, single.cfg.vocab_size, max_prompt=32, max_new=24,
                      seed=0)
    ref = single.run(reqs)
    fleet = router.run(reqs)
    assert fleet.peak_in_flight >= 2.5 * ref.peak_active
    assert _tokens(fleet) == _tokens(ref)
    # fleet drains the trace in fewer lockstep rounds than one engine
    rounds = max(s.decode_steps for s in fleet.replica_stats)
    assert rounds < ref.decode_steps


# ---------------------------------------------------------------------------
# Conservation property: nothing dropped, nothing duplicated


@settings(max_examples=6, deadline=None)
@given(trace_seed=st.integers(min_value=0, max_value=40),
       n=st.integers(min_value=4, max_value=10),
       policy=st.sampled_from(["round_robin", "least_loaded",
                               "prefix_affinity"]))
def test_router_conserves_requests_across_preemptions(trace_seed, n, policy):
    """For random Zipf traces over a mixed fleet whose scarce replica
    preempts: every admitted request completes exactly once, on exactly
    one replica — no duplicated or dropped ids."""
    scarce = engine_for(page_size=8, num_pages=13)  # 96 KV tokens
    roomy = engine_for()
    router = router_for([scarce, roomy], policy)
    reqs = zipf_trace(n, roomy.cfg.vocab_size, max_prompt=16, max_new=12,
                      seed=trace_seed)
    stats = router.run(reqs)
    assert [r.rid for r in stats.results] == list(range(n))
    assert sorted(stats.replica_of) == list(range(n))
    # each rid completed on exactly one replica: per-replica results are
    # disjoint and cover the trace
    per_replica = [[r.rid for r in s.results] for s in stats.replica_stats]
    flat = sorted(rid for rids in per_replica for rid in rids)
    assert flat == list(range(n))
    for rids in per_replica:
        assert len(set(rids)) == len(rids)
    for req, res in zip(reqs, stats.results):
        assert 1 <= len(res.tokens) <= req.max_new_tokens
