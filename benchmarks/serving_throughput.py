"""Serving throughput: scheduling policies x replicas x serving features.

Comparisons over the same jitted steps and seeded Zipf traces
(heavy-tailed prompt and generation lengths — the regime real serving
traffic lives in):

1. **static vs continuous** (PR 1): gang scheduling burns decode steps
   waiting for each batch's longest request; continuous batching refills
   freed slots between steps (~2x on the Zipf trace).
2. **a tight HBM budget** (PR 2): a deliberately tight benchmark target
   whose budget holds only ``TIGHT_SLOTS`` worst-case requests' KV; the
   paged pool spends it on pages and admits requests by *actual* tokens,
   reported as HBM bytes per admitted token.
3. **router vs single engine** (PR 3): the same tight-budget Zipf
   trace through a ``least_loaded`` ``ReplicaRouter`` over ``FLEET``
   replicas of ``TIGHT_SLOTS`` slots vs one such engine — fleet tok/s,
   aggregate in-flight, and load imbalance (max/mean peak resident
   tokens).
4. **blocking vs chunked prefill** (PR 4): a long-prompt-heavy trace
   (``longprompt_trace`` — the prefill-stall regime) through the same
   fleet with prompt ingestion blocking at dispatch vs chunked and
   interleaved with decode ticks.  Compared on the deterministic
   **TTFT step proxy** (virtual clock: one unit per jitted invocation,
   blocking prefills priced serially at their chunk-equivalents, round
   cost = busiest replica) — chunked must be strictly lower.
5. **cold vs prefix-cached shared prefixes** (PR 5): a trace whose
   prompts open with Zipf-clustered shared heads (``sharedprefix_trace``)
   through a paged ``prefix_affinity`` fleet with the shared-prefix KV
   cache off vs on.  The cached fleet must prefill strictly fewer
   prompt tokens (hit rate > 0) while emitting bit-identical token
   streams — reuse is free or it is a bug.
6. **gather vs fused-kernel paged decode** (PR 6): the same tight
   paged trace with ``kv_kernel='pallas'`` — the fused Pallas
   paged-attention kernel walking the page table in-kernel instead of
   materializing the (slots, max_pages*page_size, K, dh) gather each
   tick.  Gated to be token-identical to the gather path in float32 (in
   bfloat16 the two agree to the dtype's rounding, so a near tie may
   part their streams); wall time on CPU is interpret-mode emulation
   (the bytes-moved win is quoted by ``benchmarks/kernel_bench.py``'s
   ``kernel_paged_decode_*`` cells).
7. **spec-off vs draft-then-verify decode** (this PR): a repetitive
   greedy trace (``repetitive_trace`` over the 4-token-vocab
   ``picolm-4-smoke``, whose streams settle into n-gram-predictable
   cycles — the stand-in for template/boilerplate traffic) through the
   same paged engine with ``spec_k=0`` vs ``spec_k=4``.  Gated on
   bit-identical token streams AND accepted-tokens/verify-step > 1 —
   the spec path must buy multi-token ticks or it is dead weight.
8. **fixed vs autoscaling fleet under open-loop Poisson traffic**
   (this PR): the same Zipf trace stamped with Poisson
   ``arrival_vstep``s (exponential gaps on the VIRTUAL step clock —
   never wall time) through a 1-replica router vs an autoscaling
   1..``FLEET`` router with a TTFT SLO.  Gated on bit-identical
   streams vs the closed-loop replay of the same trace (arrival
   timing moves latency, never sampling) AND the autoscaler strictly
   beating the fixed fleet on both goodput-under-SLO and p99 TTFT
   (vsteps).  The regression gate then guards ``p99_ttft_steps``
   (ceiling) and ``goodput_tokens`` (floor) — wall-clock never enters
   an SLO metric.

The policy cells run with ``prefill_chunk=0`` (blocking) so their
decode-step counts stay comparable across baselines; the
``longprompt_*`` cells carry the chunked-prefill trajectory.

``--smoke`` runs a tiny version of the full grid and writes
``BENCH_serving.json`` with tokens/sec and HBM-bytes-per-admitted-token
per cell plus the fleet metrics, so CI tracks the perf trajectory;
``--check-baseline`` additionally fails if any cell's throughput
regressed more than ``REGRESSION_TOLERANCE`` vs the checked-in baseline
— enforced on deterministic tokens-per-decode-step AND the TTFT step
proxy (the components of latency/throughput the code controls;
wall-clock on shared CI runners swings with load and is advisory only).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SLOTS = 8
MAX_LEN = 128
N_REQUESTS = 32
TRACE_SEED = 0
TIGHT_SLOTS = 3          # worst-case requests' KV the tight target holds
FLEET = 3                # router replicas in the fleet comparison
REGRESSION_TOLERANCE = 0.20   # max fractional tok/s drop vs baseline
ARCH = "deepseek-7b-smoke"
SPEC_ARCH = "picolm-4-smoke"  # 4-token-vocab probe: n-gram-predictable
#                               greedy streams, the spec-decode regime
SPEC_K = 4               # draft tokens per verify step in the spec cells
OPENLOOP_GAP = 6.0       # mean Poisson inter-arrival gap, virtual steps
OPENLOOP_SEED = 3        # arrival-process seed (trace seed stays TRACE_SEED)
OPENLOOP_SLO_TTFT = 20   # TTFT goodput deadline, vsteps — sits between the
#                          autoscaled and fixed fleets' p99 so the goodput
#                          separation the autoscaler buys is visible
OPENLOOP_SLO_E2E = 120   # end-to-end goodput deadline, vsteps


def _kv_token_bytes(cfg) -> int:
    from repro.core.tuning import kv_bytes_per_token
    return kv_bytes_per_token(cfg)


def _register_tight_target(max_len: int = MAX_LEN) -> str:
    """A CPU target whose HBM budget holds only TIGHT_SLOTS worst-case
    requests' KV — the regime where the page pool's
    tokens-not-worst-cases accounting shows up."""
    from repro.configs.base import get_config
    from repro.core.target import TARGETS, TargetSpec, register
    from repro.core.tuning import param_count_estimate

    name = "bench:serve-tight"
    if name in TARGETS:
        return name
    cfg = get_config(ARCH)
    param_bytes = 2 * param_count_estimate(cfg)
    kv_budget = (TIGHT_SLOTS + 0.5) * _kv_token_bytes(cfg) * max_len
    register(TargetSpec(
        name=name, chip="cpu", mesh_shape=(1,), mesh_axes=("data",),
        peak_flops=5e10, hbm_bw=2e10,
        hbm_bytes=(param_bytes + kv_budget) / 0.85, ici_bw=1e9,
        scheduler="local", kernels="reference",
        description=f"serving-bench budget target: ~{TIGHT_SLOTS} "
                    f"requests x {max_len} tokens of KV"))
    return name


def _register_f32(arch: str) -> str:
    """`arch` in float32 (parameters and activations, so the KV pool
    too), registered under its own name; returns the name."""
    import jax.numpy as jnp
    from repro.configs.base import ARCHS, get_config

    name = arch + "-f32"
    if name not in ARCHS:
        cfg = get_config(arch).replace(name=name, param_dtype=jnp.float32,
                                       activation_dtype=jnp.float32)
        ARCHS[name] = {"full": cfg, "smoke": cfg, "skip_shapes": (),
                       "is_smoke": True}
    return name


def _engine(target: str = "local:cpu", slots: int = SLOTS,
            max_len: int = MAX_LEN, kv_kernel: str = "auto",
            arch: str = ARCH):
    from repro.serving import ServeEngine
    return ServeEngine(arch=arch, target=target, num_slots=slots,
                       max_len=max_len, seed=0, kv_kernel=kv_kernel,
                       log=lambda *a, **k: None)


def _pool_bytes(engine) -> int:
    return engine.num_pages * engine.page_size * _kv_token_bytes(engine.cfg)


def _trace(n: int, engine, max_new: int = 64, seed: int = TRACE_SEED):
    from repro.serving import zipf_trace
    return zipf_trace(n, engine.cfg.vocab_size, max_prompt=48,
                      max_new=max_new, alpha=1.3, seed=seed)


def _router(engine, fleet: int = FLEET, policy: str = "least_loaded"):
    """A fleet of `fleet` replicas of `engine` — the same object repeated,
    so the jitted steps compile once and only the pools are per-replica
    (each replica models a host with the engine's full HBM budget)."""
    from repro.serving import ReplicaRouter
    return ReplicaRouter([engine] * fleet, policy=policy,
                         log=lambda *a, **k: None)


def _bytes_per_token(engine, stats) -> float:
    """Pool HBM bytes per admitted *resident* token at peak occupancy —
    the over-reservation metric: a pool that pinned max_len per request
    however short it is would keep its peak resident tokens far below
    capacity and the ratio high."""
    return _pool_bytes(engine) / max(stats.peak_resident_tokens, 1)


def _longprompt(n: int, engine, max_new: int = 8, seed: int = TRACE_SEED):
    """Prompts clustered near max_len, short generations — the regime
    where admission-time prefill stalls dominate."""
    from repro.serving import longprompt_trace
    return longprompt_trace(n, engine.cfg.vocab_size, max_prompt=MAX_LEN,
                            max_new=max_new, seed=seed)


def _sharedprefix(n: int, engine, seed: int = TRACE_SEED):
    """Prompts opening with Zipf-clustered shared heads (two 16-token
    pages each) — the regime where prefix-cache page reuse shows up."""
    from repro.serving import sharedprefix_trace
    return sharedprefix_trace(n, engine.cfg.vocab_size, seed=seed)


def _spec_engine(target: str = "local:cpu"):
    """An engine on the 4-token-vocab probe arch — the only extra
    compile the spec cells pay (picolm shares deepseek-7b-smoke's layer
    shapes except the tiny vocab head)."""
    from repro.serving import ServeEngine
    return ServeEngine(arch=SPEC_ARCH, target=target, num_slots=4,
                       max_len=MAX_LEN, seed=0, log=lambda *a, **k: None)


def _repetitive(n: int, engine, max_new: int = 48, seed: int = TRACE_SEED):
    """Short cyclic prompts, long greedy generations — the regime where
    the n-gram drafter's accepted-tokens/verify-step clears 1."""
    from repro.serving import repetitive_trace
    return repetitive_trace(n, engine.cfg.vocab_size, max_new=max_new,
                            seed=seed)


def _num(x, nd: int = 4):
    """Round for the JSON emitter; NaN (e.g. imbalance of an idle fleet)
    becomes None — valid strict JSON instead of a bare NaN literal."""
    return None if x != x else round(x, nd)


def _timed(fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` under the wall clock; returns
    ``(result, seconds)`` — the one timing idiom every cell shares."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def run(report) -> None:
    engine = _engine()
    reqs = _trace(N_REQUESTS, engine)
    # warm ALL jit caches the trace will touch (every prompt-length bucket
    # compiles its own prefill/insert) so neither timed run pays compile
    engine.run(reqs, policy="continuous")

    static, t_static = _timed(engine.run, reqs, policy="static")
    cont, t_cont = _timed(engine.run, reqs, policy="continuous")

    speedup = cont.tokens_per_s / max(static.tokens_per_s, 1e-9)
    report("serve_static_batching",
           t_static / max(static.decode_steps, 1) * 1e6,
           f"{static.tokens_per_s:.1f} tok/s; {static.decode_steps} steps; "
           f"occupancy {static.occupancy:.0%}")
    report("serve_continuous_batching",
           t_cont / max(cont.decode_steps, 1) * 1e6,
           f"{cont.tokens_per_s:.1f} tok/s; {cont.decode_steps} steps; "
           f"occupancy {cont.occupancy:.0%}; speedup {speedup:.2f}x")

    # --- the long-tail trace under one tight HBM budget -----------------
    tight = _register_tight_target()
    e_paged = _engine(target=tight)
    ltrace = _trace(N_REQUESTS, e_paged)
    e_paged.run(ltrace, policy="continuous")      # warm
    s_paged, t_p = _timed(e_paged.run, ltrace, policy="continuous")
    report("serve_paged_tight_budget",
           t_p / max(s_paged.decode_steps, 1) * 1e6,
           f"{s_paged.tokens_per_s:.1f} tok/s; {e_paged.num_slots} slots; "
           f"peak {s_paged.peak_active} in flight; "
           f"{_bytes_per_token(e_paged, s_paged):.0f} B/admitted-token; "
           f"{s_paged.preemptions} preemptions")

    # --- router over a fleet of TIGHT_SLOTS-slot replicas vs one --------
    e_fleet = _engine(target=tight, slots=TIGHT_SLOTS)
    s_one = e_fleet.run(ltrace, policy="continuous")     # warms the fleet
    router = _router(e_fleet)
    s_fleet, t_f = _timed(router.run, ltrace, policy="continuous")
    steps = max(max(s.decode_steps for s in s_fleet.replica_stats), 1)
    report("serve_router_least_loaded_fleet",
           t_f / steps * 1e6,
           f"{s_fleet.tokens_per_s:.1f} tok/s fleet over "
           f"{FLEET} replicas (single: {s_one.tokens_per_s:.1f}); peak "
           f"{s_fleet.peak_in_flight} in flight "
           f"({s_fleet.peak_in_flight / max(s_one.peak_active, 1):.1f}x "
           f"single); imbalance {s_fleet.imbalance:.2f}; "
           f"{s_fleet.reroutes} reroutes")

    # --- blocking vs chunked prefill on the long-prompt trace ------------
    ptrace = _longprompt(N_REQUESTS, e_fleet)
    router.run(ptrace, policy="continuous", prefill_chunk=0)      # warm
    router.run(ptrace, policy="continuous")
    p_block, t_b = _timed(router.run, ptrace, policy="continuous",
                          prefill_chunk=0)
    p_chunk, t_c2 = _timed(router.run, ptrace, policy="continuous")
    report("serve_longprompt_router_blocking", t_b * 1e6,
           f"mean TTFT {p_block.mean_ttft_steps:.1f} vsteps; "
           f"{p_block.tokens_per_s:.1f} tok/s fleet")
    report("serve_longprompt_router_chunked", t_c2 * 1e6,
           f"mean TTFT {p_chunk.mean_ttft_steps:.1f} vsteps "
           f"({p_block.mean_ttft_steps / max(p_chunk.mean_ttft_steps, 1e-9):.2f}x "
           f"lower); {p_chunk.tokens_per_s:.1f} tok/s fleet; "
           f"{p_chunk.prefill_chunks} chunks, "
           f"{p_chunk.overlap_steps} overlapped ticks")

    # --- shared-prefix trace: cold vs prefix-cached paged fleet ----------
    sp_router = _router(e_paged, policy="prefix_affinity")
    strace = _sharedprefix(N_REQUESTS, e_paged)
    sp_router.run(strace)                                         # warm
    sp_router.run(strace, prefix_cache=True)
    sp_cold, t_sc = _timed(sp_router.run, strace)
    sp_hot, t_sh = _timed(sp_router.run, strace, prefix_cache=True)
    report("serve_sharedprefix_router_cold", t_sc * 1e6,
           f"{sp_cold.prefill_tokens} prompt tokens prefilled; "
           f"mean TTFT {sp_cold.mean_ttft_steps:.1f} vsteps; "
           f"{sp_cold.tokens_per_s:.1f} tok/s fleet")
    report("serve_sharedprefix_router_cached", t_sh * 1e6,
           f"{sp_hot.prefill_tokens} prompt tokens prefilled "
           f"({sp_hot.prefill_tokens_saved} saved, hit rate "
           f"{sp_hot.prefix_hit_rate:.0%}); mean TTFT "
           f"{sp_hot.mean_ttft_steps:.1f} vsteps; "
           f"{sp_hot.tokens_per_s:.1f} tok/s fleet")

    # --- spec-off vs draft-then-verify on the repetitive trace -----------
    e_spec = _spec_engine()
    rtrace = _repetitive(N_REQUESTS, e_spec)
    e_spec.run(rtrace, spec_k=0, prefill_chunk=0)               # warm
    e_spec.run(rtrace, spec_k=SPEC_K, prefill_chunk=0)
    spc_off, t_o = _timed(e_spec.run, rtrace, spec_k=0, prefill_chunk=0)
    spc_on, t_v = _timed(e_spec.run, rtrace, spec_k=SPEC_K,
                         prefill_chunk=0)
    report("serve_repetitive_spec_off",
           t_o / max(spc_off.decode_steps, 1) * 1e6,
           f"{spc_off.tokens_per_s:.1f} tok/s; "
           f"{spc_off.decode_steps} steps")
    report("serve_repetitive_spec_on",
           t_v / max(spc_on.decode_steps, 1) * 1e6,
           f"{spc_on.tokens_per_s:.1f} tok/s; {spc_on.decode_steps} steps "
           f"({spc_off.decode_steps / max(spc_on.decode_steps, 1):.2f}x "
           f"fewer); {spc_on.accepted_per_verify:.2f} tokens/verify")


def run_smoke(out_path: str = "BENCH_serving.json",
              n_requests: int = 12, max_new: int = 32,
              check_baseline: bool = False) -> dict:
    """Tiny grid (both policies, plus the router fleet) on the
    tight-budget target; emits tokens/sec and
    HBM-bytes-per-admitted-token per cell and the fleet metrics.  With
    ``check_baseline`` the previous ``out_path`` contents gate the run:
    any cell regressing more than REGRESSION_TOLERANCE in tok/s fails."""
    baseline = None
    if check_baseline:
        if not Path(out_path).exists():
            # a missing baseline must not silently disable the gate
            raise SystemExit(f"SMOKE FAIL: --check-baseline but no "
                             f"checked-in {out_path} to compare against")
        baseline = json.loads(Path(out_path).read_text())
    tight = _register_tight_target()
    cells = {}
    single_paged = _engine(target=tight)
    reqs = _trace(n_requests, single_paged, max_new=max_new)
    single_paged.run(reqs, policy="continuous", prefill_chunk=0)  # warm
    for policy in ("static", "continuous"):
        # blocking prefill keeps these cells' decode-step counts
        # comparable with pre-chunking baselines; the longprompt cells
        # below track the chunked path
        stats = single_paged.run(reqs, policy=policy, prefill_chunk=0)
        if policy == "continuous":
            paged_cont_stats = stats
        cells[f"paged_{policy}"] = {
            "tokens_per_s": round(stats.tokens_per_s, 2),
            "tokens_per_step": round(
                stats.generated_tokens / max(stats.decode_steps, 1), 4),
            "hbm_bytes_per_admitted_token":
                round(_bytes_per_token(single_paged, stats), 1),
            "pool_bytes": _pool_bytes(single_paged),
            "slots": single_paged.num_slots,
            "decode_steps": stats.decode_steps,
            "generated_tokens": stats.generated_tokens,
            "occupancy": round(stats.occupancy, 4),
            "peak_active": stats.peak_active,
            "preemptions": stats.preemptions,
            "mean_ttft_steps": round(stats.mean_ttft_steps, 4),
        }
    # paged decode through the fused Pallas paged-attention kernel (page
    # table walked in-kernel, interpret mode on CPU): same trace, same
    # tight budget — recorded so the kernel path has a throughput
    # baseline; its float32 twin below is gated to be token-identical to
    # the gather path
    e_kernel = _engine(target=tight, kv_kernel="pallas")
    kreqs = _trace(n_requests, e_kernel, max_new=max_new)
    e_kernel.run(kreqs, policy="continuous", prefill_chunk=0)   # warm jits
    kstats = e_kernel.run(kreqs, policy="continuous", prefill_chunk=0)
    cells["paged_continuous_kernel"] = {
        "tokens_per_s": round(kstats.tokens_per_s, 2),
        "tokens_per_step": round(
            kstats.generated_tokens / max(kstats.decode_steps, 1), 4),
        "hbm_bytes_per_admitted_token":
            round(_bytes_per_token(e_kernel, kstats), 1),
        "pool_bytes": _pool_bytes(e_kernel),
        "slots": e_kernel.num_slots,
        "kv_kernel": e_kernel.kv_kernel,
        "decode_steps": kstats.decode_steps,
        "generated_tokens": kstats.generated_tokens,
        "occupancy": round(kstats.occupancy, 4),
        "peak_active": kstats.peak_active,
        "preemptions": kstats.preemptions,
        "mean_ttft_steps": round(kstats.mean_ttft_steps, 4),
    }
    f32_arch = _register_f32(ARCH)
    f32_stats = {k: _engine(target=tight, kv_kernel=k,
                            arch=f32_arch).run(kreqs, policy="continuous",
                                               prefill_chunk=0)
                 for k in ("gather", "pallas")}
    # draft-then-verify speculative decoding: the repetitive greedy trace
    # on the 4-token-vocab probe arch, same engine with spec off vs
    # spec_k=SPEC_K — gated below on bit-identical streams AND
    # accepted-tokens/verify-step > 1 (the multi-token-tick win shows up
    # in tokens_per_step, which the regression gate then guards)
    e_spec = _spec_engine()
    rtrace = _repetitive(n_requests, e_spec)
    e_spec.run(rtrace, spec_k=0, prefill_chunk=0)           # warm both
    e_spec.run(rtrace, spec_k=SPEC_K, prefill_chunk=0)      # step shapes
    spc_off = e_spec.run(rtrace, spec_k=0, prefill_chunk=0)
    spc_on = e_spec.run(rtrace, spec_k=SPEC_K, prefill_chunk=0)
    for name, k, stats in (("paged_spec_off", 0, spc_off),
                           ("paged_spec_on", SPEC_K, spc_on)):
        cells[name] = {
            "tokens_per_s": round(stats.tokens_per_s, 2),
            "tokens_per_step": round(
                stats.generated_tokens / max(stats.decode_steps, 1), 4),
            "arch": SPEC_ARCH,
            "spec_k": k,
            "decode_steps": stats.decode_steps,
            "generated_tokens": stats.generated_tokens,
            "spec_verify_steps": stats.spec_verify_steps,
            "spec_drafted_tokens": stats.spec_drafted_tokens,
            "spec_accepted_tokens": stats.spec_accepted_tokens,
            "accepted_per_verify": round(stats.accepted_per_verify, 4),
        }
    # router fleet: FLEET replicas of TIGHT_SLOTS slots each (one replica
    # cannot hold the trace, so routing and autoscaling have load to
    # spread), least-loaded routing, same trace — fleet tok/s, aggregate
    # in-flight against one such engine, and load imbalance.  The single
    # engine's run warms the jitted steps every fleet below shares (same
    # engine object, one pool shape)
    fleet_engine = _engine(target=tight, slots=TIGHT_SLOTS)
    single_peak = fleet_engine.run(reqs, policy="continuous",
                                   prefill_chunk=0).peak_active
    router = _router(fleet_engine)
    fleet = router.run(reqs, policy="continuous", prefill_chunk=0)
    rounds = max(max(s.decode_steps for s in fleet.replica_stats), 1)
    cells[f"router_least_loaded_x{FLEET}"] = {
        "tokens_per_s": round(fleet.tokens_per_s, 2),
        "tokens_per_step": round(fleet.generated_tokens / rounds, 4),
        "replicas": FLEET,
        "route_policy": "least_loaded",
        "generated_tokens": fleet.generated_tokens,
        "decode_steps": rounds,               # lockstep rounds, fleet-wide
        "peak_in_flight": fleet.peak_in_flight,
        "in_flight_vs_single":
            round(fleet.peak_in_flight / max(single_peak, 1), 2),
        "load_imbalance": _num(fleet.imbalance),
        "reroutes": fleet.reroutes,
    }
    # long-prompt trace, blocking vs chunked prompt ingestion: the TTFT
    # proxy comparison the chunked-prefill pipeline is judged on
    ptrace = _longprompt(n_requests, fleet_engine)
    # warm BOTH ingestion modes (chunked compiles the small chunk
    # buckets, blocking the whole-prompt ones) so neither timed cell
    # pays compilation
    router.run(ptrace, policy="continuous")
    router.run(ptrace, policy="continuous", prefill_chunk=0)
    for name, chunk in (("longprompt_router_blocking", 0),
                        ("longprompt_router_chunked", None)):
        stats = router.run(ptrace, policy="continuous", prefill_chunk=chunk)
        rounds = max(max(s.decode_steps for s in stats.replica_stats), 1)
        cells[name] = {
            "tokens_per_s": round(stats.tokens_per_s, 2),
            "tokens_per_step": round(stats.generated_tokens / rounds, 4),
            "mean_ttft_steps": round(stats.mean_ttft_steps, 4),
            "prefill_chunk": (0 if chunk == 0 else
                              fleet_engine.prefill_chunk),
            "prefill_chunks": stats.prefill_chunks,
            "prefill_compiles": max(
                s.prefill_compiles for s in stats.replica_stats),
            "prefill_queue_peak": max(
                s.prefill_queue_peak for s in stats.replica_stats),
            "overlap_steps": stats.overlap_steps,
            "generated_tokens": stats.generated_tokens,
            "decode_steps": rounds,
            "replicas": FLEET,
            "reroutes": stats.reroutes,
        }
    # shared-prefix trace, cache off vs on, through a prefix_affinity
    # fleet (sharers colocate, so per-replica caches
    # compose): the reuse comparison the prefix KV cache is judged on.
    # 3x the fleet-capacity request count — hits need waves that arrive
    # after an earlier sharer's prefill completed (no in-flight dedup).
    # Warm both modes — cached suffix chunks start mid-prompt, so their
    # (bucket, kv_bound) pairs can differ from the cold run's
    strace = _sharedprefix(3 * n_requests, single_paged)
    sp_router = _router(single_paged, policy="prefix_affinity")
    sp_router.run(strace, policy="continuous")
    sp_router.run(strace, policy="continuous", prefix_cache=True)
    sp_cold = sp_router.run(strace, policy="continuous")
    sp_hot = sp_router.run(strace, policy="continuous", prefix_cache=True)
    for name, stats in (("sharedprefix_router_cold", sp_cold),
                        ("sharedprefix_router_cached", sp_hot)):
        rounds = max(max(s.decode_steps for s in stats.replica_stats), 1)
        cells[name] = {
            "tokens_per_s": round(stats.tokens_per_s, 2),
            "tokens_per_step": round(stats.generated_tokens / rounds, 4),
            "mean_ttft_steps": _num(stats.mean_ttft_steps),
            "prefill_tokens": stats.prefill_tokens,
            "prefill_tokens_saved": stats.prefill_tokens_saved,
            "prefix_hits": stats.prefix_hits,
            "prefix_misses": stats.prefix_misses,
            "prefix_hit_rate": _num(stats.prefix_hit_rate),
            "generated_tokens": stats.generated_tokens,
            "decode_steps": rounds,
            "replicas": FLEET,
            "route_policy": "prefix_affinity",
            "load_imbalance": _num(stats.imbalance),
        }
    # open-loop Poisson traffic: the same Zipf trace stamped with
    # virtual-step arrivals, through a fixed 1-replica router vs an
    # autoscaling 1..FLEET router under a TTFT/e2e SLO.  All SLO and
    # percentile metrics below are vstep-derived (deterministic);
    # tokens_per_s stays the only wall-clock (advisory) field.
    import dataclasses

    from repro.serving import AutoscalePolicy, with_arrivals
    oreqs = with_arrivals(_trace(n_requests, fleet_engine, max_new=max_new),
                          "poisson", mean_gap=OPENLOOP_GAP,
                          seed=OPENLOOP_SEED)
    closed_reqs = [dataclasses.replace(r, arrival_vstep=0) for r in oreqs]
    slo = dict(slo_ttft_steps=OPENLOOP_SLO_TTFT,
               slo_e2e_steps=OPENLOOP_SLO_E2E)
    fixed_router = _router(fleet_engine, fleet=1)
    # no extra warm pass: same engine object as the cells above
    ol_closed = fixed_router.run(closed_reqs, policy="continuous",
                                 prefill_chunk=0, **slo)
    ol_fixed = fixed_router.run(oreqs, policy="continuous",
                                prefill_chunk=0, **slo)
    auto_router = _router(fleet_engine)
    ol_auto = auto_router.run(
        oreqs, policy="continuous", prefill_chunk=0,
        autoscale=AutoscalePolicy(min_replicas=1, max_replicas=FLEET),
        **slo)
    for name, stats in (("openloop_poisson_fixed", ol_fixed),
                        ("openloop_poisson_autoscale", ol_auto)):
        m = stats.to_metrics()
        cells[name] = {
            "tokens_per_s": round(stats.tokens_per_s, 2),
            "arrivals": "poisson",
            "arrival_gap": OPENLOOP_GAP,
            "arrival_seed": OPENLOOP_SEED,
            "slo_ttft_steps": OPENLOOP_SLO_TTFT,
            "slo_e2e_steps": OPENLOOP_SLO_E2E,
            "p50_ttft_steps": _num(stats.p50_ttft_steps),
            "p99_ttft_steps": _num(stats.p99_ttft_steps),
            "p50_e2e_steps": _num(stats.p50_e2e_steps),
            "p99_e2e_steps": _num(stats.p99_e2e_steps),
            "goodput_tokens": stats.goodput_tokens,
            "generated_tokens": stats.generated_tokens,
            "total_vsteps": stats.total_vsteps,
            "peak_replicas": m["router_peak_replicas"],
            "autoscale_grows": m["router_autoscale_grows"],
            "autoscale_drains": m["router_autoscale_drains"],
            "replicas": 1 if stats is ol_fixed else FLEET,
        }
    # telemetry overhead: the exact paged/continuous drain of the
    # paged_continuous cell, re-run with a Tracer attached.  Tracing is
    # pure host-side bookkeeping on the virtual clock, so the gate below
    # demands EXACT stream and tokens-per-decode-step equality with the
    # tracing-off run — wall clock stays advisory, like everywhere else.
    from repro.serving import Tracer
    tel_tracer = Tracer()
    tel_stats = single_paged.run(
        _trace(n_requests, single_paged, max_new=max_new),
        policy="continuous", prefill_chunk=0, tracer=tel_tracer)
    cells["telemetry_overhead"] = {
        "tokens_per_s": round(tel_stats.tokens_per_s, 2),
        "tokens_per_step": round(
            tel_stats.generated_tokens / max(tel_stats.decode_steps, 1), 4),
        "decode_steps": tel_stats.decode_steps,
        "generated_tokens": tel_stats.generated_tokens,
        "trace_spans": len(tel_tracer.spans),
        "ring_events": tel_tracer.total_events,
        "mean_ttft_steps": round(tel_stats.mean_ttft_steps, 4),
    }
    out = {"arch": ARCH, "target": tight, "n_requests": n_requests,
           "max_len": MAX_LEN, "trace_seed": TRACE_SEED, "cells": cells}
    pc = cells["paged_continuous"]
    pk = cells["paged_continuous_kernel"]
    so = cells["paged_spec_off"]
    sn = cells["paged_spec_on"]
    rc = cells[f"router_least_loaded_x{FLEET}"]
    lb = cells["longprompt_router_blocking"]
    lc = cells["longprompt_router_chunked"]
    sc = cells["sharedprefix_router_cold"]
    sh = cells["sharedprefix_router_cached"]
    of_cell = cells["openloop_poisson_fixed"]
    oa_cell = cells["openloop_poisson_autoscale"]
    print(f"paged {pc['tokens_per_s']} tok/s @ "
          f"{pc['hbm_bytes_per_admitted_token']} B/tok, peak "
          f"{pc['peak_active']} (fused kernel {pk['tokens_per_s']} tok/s, "
          f"token-identical in float32) | router x{FLEET} "
          f"{rc['tokens_per_s']} "
          f"tok/s fleet, peak {rc['peak_in_flight']} "
          f"({rc['in_flight_vs_single']}x single), imbalance "
          f"{rc['load_imbalance']} | longprompt TTFT "
          f"{lc['mean_ttft_steps']} vsteps chunked vs "
          f"{lb['mean_ttft_steps']} blocking "
          f"({lc['overlap_steps']} overlapped ticks) | sharedprefix "
          f"prefill {sh['prefill_tokens']} vs {sc['prefill_tokens']} cold "
          f"({sh['prefill_tokens_saved']} saved, hit rate "
          f"{sh['prefix_hit_rate']}) | spec k={SPEC_K} "
          f"{sn['accepted_per_verify']} tok/verify, "
          f"{sn['decode_steps']} steps vs {so['decode_steps']} spec-off "
          f"(token-identical) | openloop poisson p99 TTFT "
          f"{oa_cell['p99_ttft_steps']} vsteps autoscaled "
          f"(peak {oa_cell['peak_replicas']} replicas, "
          f"{oa_cell['autoscale_grows']}g/{oa_cell['autoscale_drains']}d) "
          f"vs {of_cell['p99_ttft_steps']} fixed; goodput "
          f"{oa_cell['goodput_tokens']}t vs {of_cell['goodput_tokens']}t "
          f"under ttft<={OPENLOOP_SLO_TTFT}")
    # gates run BEFORE the write: a failing run must not replace the
    # checked-in baseline with its own (regressed) numbers
    try:
        if rc["peak_in_flight"] < 2.5 * single_peak:
            raise SystemExit(
                f"SMOKE FAIL: router fleet held {rc['peak_in_flight']} in "
                f"flight, < 2.5x the single engine's {single_peak}")
        if not lc["mean_ttft_steps"] < lb["mean_ttft_steps"]:
            raise SystemExit(
                f"SMOKE FAIL: chunked prefill mean TTFT "
                f"{lc['mean_ttft_steps']} vsteps is not strictly lower "
                f"than blocking's {lb['mean_ttft_steps']} on the "
                f"long-prompt trace")
        sp_tok = lambda stats: [r.tokens for r in stats.results]  # noqa: E731
        if sp_tok(f32_stats["pallas"]) != sp_tok(f32_stats["gather"]):
            raise SystemExit(
                "SMOKE FAIL: float32 fused-kernel paged token streams "
                "differ from the gather path on the same trace — in "
                "float32 the kernel must be token-identical")
        if sp_tok(sp_hot) != sp_tok(sp_cold):
            raise SystemExit(
                "SMOKE FAIL: prefix-cached token streams differ from the "
                "cache-off run on the shared-prefix trace — reuse must "
                "never change output")
        if sp_tok(spc_on) != sp_tok(spc_off):
            raise SystemExit(
                "SMOKE FAIL: speculative token streams differ from the "
                "spec-off run on the repetitive trace — draft-then-verify "
                "must be bit-identical to sequential decode")
        if not sn["accepted_per_verify"] > 1.0:
            raise SystemExit(
                f"SMOKE FAIL: accepted-tokens/verify-step "
                f"{sn['accepted_per_verify']} <= 1 on the repetitive "
                f"trace — the drafter is accepting nothing and every "
                f"verify is a wasted wide step")
        if not sh["prefill_tokens_saved"] > 0:
            raise SystemExit(
                "SMOKE FAIL: prefix cache saved no prefill tokens on the "
                "shared-prefix trace (hit rate "
                f"{sh['prefix_hit_rate']}) — the reuse layer is dead")
        if sh["prefill_tokens"] + sh["prefill_tokens_saved"] != \
                sc["prefill_tokens"]:
            raise SystemExit(
                "SMOKE FAIL: cached prefill tokens + saved tokens != cold "
                f"prefill tokens ({sh['prefill_tokens']} + "
                f"{sh['prefill_tokens_saved']} vs {sc['prefill_tokens']}) "
                "— the savings accounting leaks")
        tok_by_rid = lambda stats: {r.rid: r.tokens  # noqa: E731
                                    for r in stats.results}
        if tok_by_rid(ol_fixed) != tok_by_rid(ol_closed) or \
                tok_by_rid(ol_auto) != tok_by_rid(ol_closed):
            raise SystemExit(
                "SMOKE FAIL: open-loop token streams differ from the "
                "closed-loop replay of the same trace — arrival timing "
                "and autoscaling must move latency, never sampling")
        if not of_cell["goodput_tokens"] < oa_cell["goodput_tokens"] or \
                not oa_cell["goodput_tokens"] == \
                oa_cell["generated_tokens"]:
            raise SystemExit(
                f"SMOKE FAIL: autoscaled goodput "
                f"{oa_cell['goodput_tokens']}t under the "
                f"{OPENLOOP_SLO_TTFT}-vstep TTFT SLO must beat the fixed "
                f"fleet's {of_cell['goodput_tokens']}t and cover all "
                f"{oa_cell['generated_tokens']}t generated — scaling out "
                f"is buying nothing")
        if not (oa_cell["p99_ttft_steps"] or 0) < \
                (of_cell["p99_ttft_steps"] or float("inf")):
            raise SystemExit(
                f"SMOKE FAIL: autoscaled p99 TTFT "
                f"{oa_cell['p99_ttft_steps']} vsteps is not strictly "
                f"below the fixed fleet's {of_cell['p99_ttft_steps']}")
        if not oa_cell["autoscale_grows"] > 0:
            raise SystemExit(
                "SMOKE FAIL: the autoscaler never grew under Poisson "
                "load — the open-loop cell is not exercising scaling")
        tel = cells["telemetry_overhead"]
        if sp_tok(tel_stats) != sp_tok(paged_cont_stats):
            raise SystemExit(
                "SMOKE FAIL: telemetry-on token streams differ from the "
                "tracing-off paged_continuous run — tracing must be "
                "observationally free")
        if tel["tokens_per_step"] != pc["tokens_per_step"] or \
                tel["decode_steps"] != pc["decode_steps"]:
            raise SystemExit(
                f"SMOKE FAIL: telemetry-on tokens/step "
                f"{tel['tokens_per_step']} @ {tel['decode_steps']} steps "
                f"!= tracing-off {pc['tokens_per_step']} @ "
                f"{pc['decode_steps']} — tracing moved the schedule")
        if not tel["trace_spans"]:
            raise SystemExit(
                "SMOKE FAIL: the telemetry run recorded no spans — the "
                "tracer hook is dead")
        if baseline is not None:
            _check_regression(baseline, out, out_path)
    except SystemExit:
        print("fresh cells (NOT written):\n" + json.dumps(cells, indent=2))
        raise
    if baseline is not None and \
            _strip_wall(baseline.get("cells", {})) == _strip_wall(cells):
        # deterministic metrics are bit-identical: rewriting would only
        # churn this machine's wall-clock numbers into the tracked file
        print(f"{out_path} unchanged (deterministic metrics match "
              f"baseline); not rewritten")
    else:
        Path(out_path).write_text(json.dumps(out, indent=2))
        print(f"wrote {out_path}")
    return out


def _strip_wall(cells: dict) -> dict:
    """Cells without their machine-dependent wall-clock field."""
    return {n: {k: v for k, v in c.items() if k != "tokens_per_s"}
            for n, c in cells.items()}


def _check_regression(baseline: dict, fresh: dict,
                      out_path: str = "BENCH_serving.json") -> None:
    """Fail when a cell's throughput regresses > REGRESSION_TOLERANCE vs
    the checked-in baseline.

    The *enforced* metrics are ``tokens_per_step`` (generated tokens per
    decode step — the machine-independent component of tok/s, exactly
    what a batching/routing regression moves), the ``mean_ttft_steps``
    proxy (deterministic like tokens/step; lower is better, so the gate
    is a ceiling), ``p99_ttft_steps`` / ``goodput_tokens`` (the
    open-loop SLO metrics — vstep percentiles gate as ceilings, goodput
    as a floor; an idle fleet's NaN percentile serializes to null and
    skips the gate rather than tripping it), and
    ``prefill_tokens_saved`` (the prefix cache's reuse, which must stay
    strictly positive wherever the baseline had it).  Each metric guards **independently**: a baseline cell that
    predates one metric must not silently skip the others' gates.
    Wall-clock tok/s swings 2-3x with CI-runner load on these sub-second
    cells, so it is reported as an advisory only.  Cells that vanished
    from the grid fail (a silently dropped comparison is a regression in
    coverage, not just speed) — and cells *new* to the grid fail too:
    an ungated cell ships no protection, so the baseline file must be
    refreshed in the same PR that adds the cell."""
    old_cells = baseline.get("cells", {})
    missing = [n for n in old_cells if n not in fresh["cells"]]
    if missing:
        raise SystemExit("SMOKE FAIL: cells missing from fresh run vs "
                         "checked-in baseline: " + ", ".join(missing))
    added = [n for n in fresh["cells"] if n not in old_cells]
    if added:
        raise SystemExit(
            f"SMOKE FAIL: {len(added)} new cell(s) not in baseline — "
            f"refresh {out_path} in this PR so they are gated from day "
            f"one: " + ", ".join(sorted(added)))
    bad = []
    for name in sorted(old_cells):
        old, new = old_cells[name], fresh["cells"][name]
        if "tokens_per_step" in old:
            floor = old["tokens_per_step"] * (1.0 - REGRESSION_TOLERANCE)
            if new.get("tokens_per_step", 0.0) < floor:
                bad.append(
                    f"{name}: {new.get('tokens_per_step')} tokens/step < "
                    f"{floor:.3f} (baseline {old['tokens_per_step']} "
                    f"- {REGRESSION_TOLERANCE:.0%})")
        if (old.get("mean_ttft_steps") or 0) > 0:
            ceiling = old["mean_ttft_steps"] * (1.0 + REGRESSION_TOLERANCE)
            if (new.get("mean_ttft_steps") or 0) > ceiling:
                bad.append(
                    f"{name}: {new.get('mean_ttft_steps')} TTFT vsteps > "
                    f"{ceiling:.3f} (baseline {old['mean_ttft_steps']} "
                    f"+ {REGRESSION_TOLERANCE:.0%})")
        # percentile/goodput gates (open-loop cells): vstep-derived and
        # deterministic like mean_ttft_steps.  `or 0` maps the null an
        # idle fleet's NaN percentile serializes to — a baseline (or
        # fresh) null never trips a gate, it just skips it.
        if (old.get("p99_ttft_steps") or 0) > 0:
            ceiling = old["p99_ttft_steps"] * (1.0 + REGRESSION_TOLERANCE)
            if (new.get("p99_ttft_steps") or float("inf")) > ceiling:
                bad.append(
                    f"{name}: {new.get('p99_ttft_steps')} p99 TTFT vsteps "
                    f"> {ceiling:.3f} (baseline {old['p99_ttft_steps']} "
                    f"+ {REGRESSION_TOLERANCE:.0%})")
        if old.get("goodput_tokens", 0) > 0:
            floor = old["goodput_tokens"] * (1.0 - REGRESSION_TOLERANCE)
            if new.get("goodput_tokens", 0) < floor:
                bad.append(
                    f"{name}: {new.get('goodput_tokens', 0)} goodput "
                    f"tokens under SLO < {floor:.1f} (baseline "
                    f"{old['goodput_tokens']} "
                    f"- {REGRESSION_TOLERANCE:.0%})")
        if old.get("prefill_tokens_saved", 0) > 0 and \
                new.get("prefill_tokens_saved", 0) <= 0:
            bad.append(f"{name}: prefix cache saved "
                       f"{new.get('prefill_tokens_saved', 0)} prefill "
                       f"tokens (baseline {old['prefill_tokens_saved']}) "
                       f"— reuse went dead")
        if "tokens_per_s" in old and \
                new.get("tokens_per_s", 0.0) < \
                old["tokens_per_s"] * (1.0 - REGRESSION_TOLERANCE):
            print(f"advisory: {name} wall-clock {new.get('tokens_per_s')} "
                  f"tok/s below baseline {old['tokens_per_s']} - "
                  f"{REGRESSION_TOLERANCE:.0%} (not enforced: wall time "
                  f"tracks runner load, tokens/step tracks the code)")
    if bad:
        raise SystemExit("SMOKE FAIL: deterministic-metric regression vs "
                         "checked-in baseline:\n  " + "\n  ".join(bad))
    print(f"baseline check OK: {len(old_cells)} cells within "
          f"{REGRESSION_TOLERANCE:.0%} of checked-in tokens/step + "
          f"TTFT vsteps (+ prefix-cache savings alive)")


def main():
    if "--smoke" in sys.argv[1:]:
        run_smoke(check_baseline="--check-baseline" in sys.argv[1:])
        return

    def report(name, us, derived=""):
        print(f"{name},{us:.3f},{derived}")
    print("name,us_per_call,derived")
    run(report)


if __name__ == "__main__":
    main()
