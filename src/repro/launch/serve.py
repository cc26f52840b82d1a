"""Serving driver (EASEY RUN command `serve ...`) — thin CLI over the
continuous-batching ServeEngine (repro/serving/).

Dense/MoE families go through the engine: a KV-cache pool sized by the
tuner's serve-mode branch (``--kv-layout contiguous`` reserves
slots x max_len worst cases; ``--kv-layout paged`` buys a page pool with
the same budget and admits by actual tokens), slot-wise decode with
per-request sampling (``--temperature`` / ``--top-k``), and a scheduler
that refills freed slots between steps.  Families without a
slot-indexable attention cache (SSM, hybrid, enc-dec, VLM) keep the
legacy fixed-batch path so `serve --arch xlstm-1.3b-smoke` still works.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import time
from pathlib import Path

import numpy as np

from repro.configs.base import get_config

# synthetic request mixes the engine/router paths can serve
TRACES = ("uniform", "zipf", "longprompt", "sharedprefix", "repetitive")


def _make_trace(name: str, n: int, vocab: int, prefill_len: int,
                decode_tokens: int, seed: int, temperature: float,
                top_k: int, top_p: float = 1.0, page_size: int = 0):
    from repro.serving import (longprompt_trace, repetitive_trace,
                               sharedprefix_trace, uniform_trace, zipf_trace)
    kw = dict(max_new=decode_tokens, seed=seed, temperature=temperature,
              top_k=top_k, top_p=top_p)
    if name == "zipf":
        return zipf_trace(n, vocab, max_prompt=prefill_len, **kw)
    if name == "longprompt":
        return longprompt_trace(n, vocab, max_prompt=prefill_len, **kw)
    if name == "repetitive":
        return repetitive_trace(n, vocab, prompt_len=prefill_len, **kw)
    if name == "sharedprefix":
        # head = half the prompt budget, aligned to the pool's REAL page
        # size so the prefix cache has whole pages to reuse (a head
        # aligned to anything else never fully covers a page and the
        # cache silently goes dead); suffixes fill the rest.  A prompt
        # budget too small for an aligned head degrades to an unaligned
        # one — fewer/no hits, but never an over-max_len trace.
        ps = page_size or 16
        head = prefill_len // 2 // ps * ps
        if head < 1:
            head = max(min(ps, prefill_len - 1), 1)
        return sharedprefix_trace(n, vocab, head_len=head,
                                  max_suffix=max(prefill_len - head, 1),
                                  **kw)
    return uniform_trace(n, vocab, prompt_len=prefill_len, **kw)


def _auto_repetitiveness(spec_k, trace, n, vocab, prefill_len,
                         decode_tokens, seed, temperature, top_k, top_p,
                         page_size) -> float:
    """The tuner hint behind ``--spec-k auto`` (``spec_k=None``).

    Measures ``trace_repetitiveness`` on a PREVIEW build of the trace —
    the real trace for the single-engine path (``_make_trace`` is
    deterministic, so the preview and the served trace agree token for
    token).  The one wart: the preview cannot see a tuner-sized pool yet,
    so ``sharedprefix`` head alignment falls back to ``page_size or 16``
    — the tuner's own default page size, so the figures only diverge
    under an explicit nonstandard ``--page-size`` (and repetitiveness is
    a *hint*, not a correctness input: any value yields bit-identical
    streams)."""
    if spec_k is not None:      # explicit k (or 0/off): no hint needed
        return 0.0
    from repro.serving import trace_repetitiveness
    preview = _make_trace(trace, n, vocab, prefill_len, decode_tokens,
                          seed, temperature, top_k, top_p,
                          page_size=page_size or 16)
    return trace_repetitiveness(preview)


def _resolve_slo(slo_ttft: int, slo_e2e: int, plan) -> tuple[int, int]:
    """-1 = adopt the tuner's napkin deadlines (``plan.serve_slo_*``)."""
    if slo_ttft < 0:
        slo_ttft = int(getattr(plan, "serve_slo_ttft_steps", 0))
    if slo_e2e < 0:
        slo_e2e = int(getattr(plan, "serve_slo_e2e_steps", 0))
    return slo_ttft, slo_e2e


def serve_main(arch: str = "deepseek-7b-smoke", batch: int = 4,
               prefill_len: int = 64, decode_tokens: int = 16,
               target: str | None = None, seed: int = 0,
               mode: str = "continuous", requests: int = 0,
               max_len: int = 0, kv_layout: str = "contiguous",
               page_size: int = 0, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0, replicas: int = 1,
               route_policy: str = "least_loaded",
               prefill_chunk: int | None = None,
               prefix_cache: bool = False, kv_kernel: str = "auto",
               spec_k: int | None = 0,
               trace: str = "uniform", arrivals: str = "closed",
               arrival_gap: float = 4.0, slo_ttft: int = 0,
               slo_e2e: int = 0, admission: str = "queue",
               autoscale: int = 0, trace_out: str | None = None,
               metrics_out: str | None = None,
               prom_out: str | None = None,
               profile_dir: str | None = None, log=print) -> dict:
    """Serve `requests` requests (default: one per slot) of `prefill_len`
    prompts, `decode_tokens` generations each.  Reports per-request latency
    and aggregate tokens/sec.  With ``replicas`` > 1 the requests flow
    through a ``ReplicaRouter`` over N tuner-split engines (``kv_layout``
    may be comma-separated to mix layouts; ``route_policy`` picks the
    balancing rule).  ``prefill_chunk`` sets the prompt-ingestion grain
    (None: the tuner's ``plan.serve_prefill_chunk``; 0: blocking
    full-prompt prefill at admission).  ``prefix_cache`` (paged layout
    only) reuses cached shared-prefix page runs by pointer copy, so
    repeat prefixes skip their re-prefill entirely; pair it with
    ``trace='sharedprefix'`` (Zipf-clustered prompt heads) to see hits —
    the default uniform trace draws unrelated prompts.  ``kv_kernel``
    picks the paged decode attention implementation (auto | gather |
    pallas — see ``--kv-kernel`` help).  ``spec_k`` turns on draft-then-
    verify speculative decoding (k draft tokens per slot per verify step;
    0 = off; None = let the tuner pick from the trace's measured
    repetitiveness — pair with ``trace='repetitive'``); token streams
    are bit-identical with spec on or off.

    Open-loop traffic: ``arrivals`` stamps each request with an
    ``arrival_vstep`` (``poisson``: exponential gaps of mean
    ``arrival_gap`` virtual steps; ``bursty``: sinusoidally rate-
    modulated; ``closed``: everything at t=0, the legacy closed loop).
    ``slo_ttft`` / ``slo_e2e`` are goodput deadlines in VIRTUAL STEPS
    (0 = off; -1 = use the tuner's ``plan.serve_slo_*`` napkin values).
    ``admission='reject'`` (router path) sheds load up front: a request
    whose napkin-predicted TTFT already busts the SLO is rejected with a
    reason instead of queued.  ``autoscale=N`` (router path) lets the
    fleet breathe between N and ``replicas`` serving replicas (grow on
    queue depth / SLO headroom, drain idle replicas to dormant).  Token
    streams stay bit-identical to the closed-loop replay of the same
    trace — arrival timing moves latency, never sampling.

    Telemetry exports (engine and router paths): ``trace_out`` writes a
    Chrome-trace/Perfetto JSON timeline of the whole run (one "process"
    per replica, one "thread" per slot, all timestamps in virtual steps
    — byte-identical across identical runs); ``metrics_out`` writes the
    flat ``to_metrics()`` snapshot as JSON (NaN -> null); ``prom_out``
    writes the same snapshot in Prometheus text exposition format.
    ``profile_dir`` runs the drain under ``jax.profiler.trace``: the
    scheduler's ``serve.*`` spans and the device's ops, scoped by
    ``jax.named_scope``, on one clock (README, "Profiling")."""
    cfg = get_config(arch)
    if trace not in TRACES:
        raise ValueError(f"trace {trace!r} not in {tuple(TRACES)}")
    from repro.serving import ADMISSION_MODES, ARRIVAL_MODES
    if arrivals not in ARRIVAL_MODES:
        raise ValueError(f"arrivals {arrivals!r} not in {ARRIVAL_MODES}")
    if admission not in ADMISSION_MODES:
        raise ValueError(f"admission {admission!r} not in {ADMISSION_MODES}")
    if replicas == 1 and (admission != "queue" or autoscale):
        raise NotImplementedError(
            "--admission reject and --autoscale need the router path "
            "(--replicas > 1); the single engine always queues")
    if autoscale and not (1 <= autoscale <= replicas):
        raise ValueError(
            f"--autoscale {autoscale} must be in [1, --replicas={replicas}]")
    from repro.serving.engine import SERVABLE_FAMILIES
    if cfg.family not in SERVABLE_FAMILIES:
        if trace_out or metrics_out or prom_out or profile_dir:
            raise NotImplementedError(
                f"--trace-out/--metrics-out/--prom-out/--profile-dir need "
                f"an engine-"
                f"servable family {SERVABLE_FAMILIES}; {arch} "
                f"({cfg.family}) is served by the legacy static path, "
                f"which has no scheduler to trace")
        if replicas > 1:
            raise NotImplementedError(
                f"--replicas needs an engine-servable family "
                f"{SERVABLE_FAMILIES}; {arch} ({cfg.family}) is served by "
                f"the legacy static path")
        return _legacy_serve_main(arch, batch, prefill_len, decode_tokens,
                                  target, seed, log)

    from repro.serving import ServeEngine
    pool_len = max_len or (prefill_len + decode_tokens)
    repetitiveness = _auto_repetitiveness(
        spec_k, trace, requests or batch * replicas, cfg.vocab_size,
        prefill_len, decode_tokens, seed, temperature, top_k, top_p,
        page_size)
    if replicas > 1:
        return _router_serve_main(
            arch=arch, batch=batch, prefill_len=prefill_len,
            decode_tokens=decode_tokens, target=target, seed=seed,
            mode=mode, requests=requests, pool_len=pool_len,
            kv_layout=kv_layout, page_size=page_size,
            temperature=temperature, top_k=top_k, top_p=top_p,
            replicas=replicas,
            route_policy=route_policy, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache, kv_kernel=kv_kernel,
            spec_k=spec_k, repetitiveness=repetitiveness, trace=trace,
            arrivals=arrivals, arrival_gap=arrival_gap, slo_ttft=slo_ttft,
            slo_e2e=slo_e2e, admission=admission, autoscale=autoscale,
            trace_out=trace_out, metrics_out=metrics_out,
            prom_out=prom_out, profile_dir=profile_dir, log=log)
    engine = ServeEngine(arch=arch, target=target, num_slots=batch,
                         max_len=pool_len, seed=seed, kv_layout=kv_layout,
                         page_size=page_size, prefill_chunk=prefill_chunk,
                         prefix_cache=prefix_cache, kv_kernel=kv_kernel,
                         spec_k=spec_k, repetitiveness=repetitiveness,
                         log=log)
    n = requests or engine.num_slots
    reqs = _make_trace(trace, n, cfg.vocab_size, prefill_len,
                       decode_tokens, seed, temperature, top_k, top_p,
                       page_size=engine.page_size)
    from repro.serving import with_arrivals
    reqs = with_arrivals(reqs, arrivals, mean_gap=arrival_gap, seed=seed)
    slo_ttft, slo_e2e = _resolve_slo(slo_ttft, slo_e2e, engine.plan)
    tracer = None
    if trace_out:
        from repro.serving import Tracer
        tracer = Tracer()
    with _profiled(profile_dir, log):
        stats = engine.run(reqs, policy=mode, slo_ttft_steps=slo_ttft,
                           slo_e2e_steps=slo_e2e, tracer=tracer)
    for r in stats.results:
        log(f"[serve]   req {r.rid}: {r.prompt_len}+{len(r.tokens)} tokens, "
            f"ttft {r.ttft_s*1e3:.1f}ms, latency {r.latency_s*1e3:.1f}ms")
    out = {
        "arch": arch, "batch": engine.num_slots, "prefill_len": prefill_len,
        "decode_tokens": decode_tokens, "mode": mode,
        "kv_layout": kv_layout,
        "kv_kernel": engine.kv_kernel,
        "requests": len(stats.results),
        "decode_steps": stats.decode_steps,
        "occupancy": stats.occupancy,
        "peak_active": stats.peak_active,
        "preemptions": stats.preemptions,
        "prefill_chunks": stats.prefill_chunks,
        "prefill_compiles": stats.prefill_compiles,
        "prefill_queue_peak": stats.prefill_queue_peak,
        "overlap_steps": stats.overlap_steps,
        "mean_ttft_steps": stats.mean_ttft_steps,
        "prefix_hits": stats.prefix_hits,
        "prefix_misses": stats.prefix_misses,
        "prefill_tokens_saved": stats.prefill_tokens_saved,
        "spec_k": engine.spec_k,
        "spec_verify_steps": stats.spec_verify_steps,
        "spec_drafted_tokens": stats.spec_drafted_tokens,
        "spec_accepted_tokens": stats.spec_accepted_tokens,
        "accepted_per_verify": stats.accepted_per_verify,
        "effective_top_k": stats.effective_top_k,
        "arrivals": arrivals,
        "p50_ttft_steps": stats.p50_ttft_steps,
        "p99_ttft_steps": stats.p99_ttft_steps,
        "p50_e2e_steps": stats.p50_e2e_steps,
        "p99_e2e_steps": stats.p99_e2e_steps,
        "goodput_tokens": stats.goodput_tokens,
        "slo_ttft_steps": stats.slo_ttft_steps,
        "slo_e2e_steps": stats.slo_e2e_steps,
        "metrics": stats.to_metrics(),
        "decode_s": stats.wall_s,
        "decode_tok_per_s": stats.tokens_per_s,
        "latency_mean_s": float(np.mean([r.latency_s for r in stats.results])),
        "sample": stats.results[0].tokens[:8],
        "plan": engine.plan,
    }
    log(f"[serve] {kv_layout}:{mode}: {out['decode_tok_per_s']:.1f} tok/s "
        f"aggregate, occupancy {stats.occupancy:.0%}, "
        f"peak {stats.peak_active} in flight")
    _write_telemetry(out["metrics"], tracer, trace_out, metrics_out,
                     prom_out, log)
    return out


def _router_serve_main(arch, batch, prefill_len, decode_tokens, target,
                       seed, mode, requests, pool_len, kv_layout, page_size,
                       temperature, top_k, top_p, replicas, route_policy,
                       prefill_chunk=None, prefix_cache=False,
                       kv_kernel="auto", spec_k=0, repetitiveness=0.0,
                       trace="uniform", arrivals="closed", arrival_gap=4.0,
                       slo_ttft=0, slo_e2e=0, admission="queue",
                       autoscale=0, trace_out=None, metrics_out=None,
                       prom_out=None, profile_dir=None, log=print) -> dict:
    """Multi-replica path: ReplicaRouter over N tuner-split engines."""
    from repro.serving import AutoscalePolicy, ReplicaRouter, with_arrivals
    cfg = get_config(arch)
    router = ReplicaRouter.build(
        arch=arch, target=target, replicas=replicas, kv_layout=kv_layout,
        num_slots=batch, max_len=pool_len, seed=seed, policy=route_policy,
        page_size=page_size, prefill_chunk=prefill_chunk,
        prefix_cache=prefix_cache, kv_kernel=kv_kernel,
        spec_k=spec_k, repetitiveness=repetitiveness, log=log)
    n = requests or batch * replicas
    reqs = _make_trace(trace, n, cfg.vocab_size, prefill_len,
                       decode_tokens, seed, temperature, top_k, top_p,
                       page_size=max(e.page_size for e in router.engines))
    reqs = with_arrivals(reqs, arrivals, mean_gap=arrival_gap, seed=seed)
    slo_ttft, slo_e2e = _resolve_slo(slo_ttft, slo_e2e,
                                     router.engines[0].plan)
    policy_obj = (AutoscalePolicy(min_replicas=autoscale,
                                  max_replicas=replicas)
                  if autoscale else None)
    tracer = None
    if trace_out:
        from repro.serving import Tracer
        tracer = Tracer()
    with _profiled(profile_dir, log):
        stats = router.run(reqs, policy=mode, slo_ttft_steps=slo_ttft,
                           slo_e2e_steps=slo_e2e, admission=admission,
                           autoscale=policy_obj, tracer=tracer)
    for rej in stats.rejected:
        log(f"[serve]   req {rej.rid} REJECTED at v{rej.v_reject}: "
            f"{rej.reason}")
    for r in stats.results:
        log(f"[serve]   req {r.rid} -> replica "
            f"{stats.replica_of[r.rid]}: {r.prompt_len}+{len(r.tokens)} "
            f"tokens, latency {r.latency_s*1e3:.1f}ms")
    out = {
        "arch": arch, "batch": batch, "prefill_len": prefill_len,
        "decode_tokens": decode_tokens, "mode": mode,
        "kv_layout": kv_layout, "replicas": replicas,
        "route_policy": route_policy,
        "requests": len(stats.results),
        "reroutes": stats.reroutes,
        "peak_in_flight": stats.peak_in_flight,
        "imbalance": stats.imbalance,
        "prefill_chunks": stats.prefill_chunks,
        "overlap_steps": stats.overlap_steps,
        "mean_ttft_steps": stats.mean_ttft_steps,
        "prefix_hits": stats.prefix_hits,
        "prefix_misses": stats.prefix_misses,
        "prefill_tokens_saved": stats.prefill_tokens_saved,
        "spec_k": router.engines[0].spec_k,
        "spec_verify_steps": stats.spec_verify_steps,
        "spec_drafted_tokens": stats.spec_drafted_tokens,
        "spec_accepted_tokens": stats.spec_accepted_tokens,
        "accepted_per_verify": stats.accepted_per_verify,
        "effective_top_k": stats.effective_top_k,
        "arrivals": arrivals,
        "admission": admission,
        "autoscale": autoscale,
        "rejected": len(stats.rejected),
        "metrics": stats.to_metrics(),
        "decode_s": stats.wall_s,
        "decode_tok_per_s": stats.tokens_per_s,
        "latency_mean_s": (float(np.mean([r.latency_s
                                          for r in stats.results]))
                           if stats.results else float("nan")),
        "sample": stats.results[0].tokens[:8] if stats.results else [],
        "plan": router.engines[0].plan,
    }
    log(f"[serve] {replicas}x{kv_layout}:{route_policy}:{mode}: "
        f"{out['decode_tok_per_s']:.1f} tok/s fleet, peak "
        f"{stats.peak_in_flight} in flight, imbalance "
        f"{stats.imbalance:.2f}")
    log("[serve] " + stats.summary())
    _write_telemetry(out["metrics"], tracer, trace_out, metrics_out,
                     prom_out, log)
    return out


def _profiled(profile_dir, log=print):
    """``jax.profiler.trace(profile_dir)`` around the drain, or nothing."""
    if not profile_dir:
        return contextlib.nullcontext()
    import jax
    log(f"[serve] profiling the drain -> {profile_dir}")
    return jax.profiler.trace(profile_dir)


def _write_telemetry(metrics, tracer, trace_out, metrics_out, prom_out,
                     log=print) -> None:
    """Write the post-run telemetry exports a flag asked for.

    ``metrics`` is a flat ``to_metrics()`` snapshot (its key prefix
    picks the schema); the trace file is pure virtual-step data, so two
    identical runs produce byte-identical files."""
    if not (trace_out or metrics_out or prom_out):
        return
    from repro.serving.telemetry import (ROUTER_SCHEMA, SERVE_SCHEMA,
                                         json_sanitize, prometheus_text,
                                         write_chrome_trace)
    if metrics_out:
        Path(metrics_out).write_text(
            json.dumps(json_sanitize(metrics), indent=2, sort_keys=False)
            + "\n")
        log(f"[serve] wrote metrics snapshot ({len(metrics)} keys) -> "
            f"{metrics_out}")
    if prom_out:
        schema = SERVE_SCHEMA if any(k.startswith("serve_") for k in metrics) \
            else ROUTER_SCHEMA
        Path(prom_out).write_text(prometheus_text(metrics, schema))
        log(f"[serve] wrote Prometheus exposition -> {prom_out}")
    if trace_out and tracer is not None:
        trace = write_chrome_trace(tracer, trace_out)
        log(f"[serve] wrote Chrome trace ({len(trace['traceEvents'])} "
            f"events; load in Perfetto / chrome://tracing) -> {trace_out}")


def _legacy_serve_main(arch: str, batch: int, prefill_len: int,
                       decode_tokens: int, target: str | None, seed: int,
                       log=print) -> dict:
    """Fixed-batch prefill-all/decode-all (pre-engine behaviour)."""
    import jax
    import jax.numpy as jnp

    from repro.core.appspec import AppSpec
    from repro.core.build import BuildService
    from repro.core.target import serve_target
    from repro.models.params import init_params
    from repro.models.transformer import model_for
    from repro.training.steps import build_decode_step, build_prefill_step

    app = AppSpec(arch=arch, shape="prefill_32k",
                  shape_overrides={"seq_len": prefill_len,
                                   "global_batch": batch},
                  run=f"serve --decode {decode_tokens}")
    tgt = serve_target(target)
    result = BuildService().build(app, tgt, lower=False)
    cfg = app.model_config
    model = model_for(cfg, remat="none")
    mesh = None if tgt.num_chips == 1 else result.mesh

    prefill = jax.jit(build_prefill_step(model, mesh))
    decode = jax.jit(build_decode_step(model, mesh), donate_argnums=(1,))

    rng = jax.random.PRNGKey(seed)
    params = init_params(model.param_table(), rng)
    table = model.batch_table(app.shape_config)
    from repro.data.pipeline import SyntheticSource
    req = SyntheticSource(cfg.vocab_size, seed).batch(table, 0)
    req = jax.tree.map(jnp.asarray, req)

    t0 = time.perf_counter()
    logits, cache = prefill(params, req)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    if "k" in cache:  # dense-family cache: pad seq axis for decode growth
        pad = decode_tokens
        for key in ("k", "v"):
            c = cache[key]
            cache[key] = jnp.pad(c, [(0, 0)] * 2 + [(0, pad)] +
                                 [(0, 0)] * (c.ndim - 3))

    tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    generated = [np.asarray(tokens)]
    t1 = time.perf_counter()
    for _ in range(decode_tokens - 1):
        logits, cache = decode(params, cache, tokens)
        tokens = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        generated.append(np.asarray(tokens))
    jax.block_until_ready(tokens)
    t_decode = time.perf_counter() - t1

    toks = np.concatenate(generated, axis=1)
    out = {
        "arch": arch, "batch": batch, "prefill_len": prefill_len,
        "decode_tokens": decode_tokens, "mode": "legacy-static",
        "prefill_s": t_prefill, "decode_s": t_decode,
        "decode_tok_per_s": batch * (decode_tokens - 1) / max(t_decode, 1e-9),
        "sample": toks[0][:8].tolist(),
    }
    log(f"[serve] prefill {prefill_len}x{batch} in {t_prefill:.3f}s; "
        f"decode {decode_tokens} tokens: "
        f"{out['decode_tok_per_s']:.1f} tok/s")
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="deepseek-7b-smoke")
    p.add_argument("--batch", type=int, default=4,
                   help="KV pool slots (engine) / batch size (legacy)")
    p.add_argument("--prefill", type=int, default=64)
    p.add_argument("--decode", type=int, default=16)
    p.add_argument("--mode", choices=("continuous", "static"),
                   default="continuous")
    p.add_argument("--requests", type=int, default=0,
                   help="number of requests (default: one per slot)")
    p.add_argument("--max-len", type=int, default=0,
                   help="per-slot KV capacity (default: prefill+decode)")
    p.add_argument("--kv-layout", default="contiguous",
                   help="KV memory layout: contiguous | paged; with "
                        "--replicas a comma-separated mix cycles over "
                        "replicas (e.g. paged,contiguous)")
    p.add_argument("--page-size", type=int, default=0,
                   help="tokens per KV page (paged; default: tuner's)")
    p.add_argument("--kv-kernel", choices=("auto", "gather", "pallas"),
                   default="auto",
                   help="paged decode attention implementation: 'gather' "
                        "reads K/V back through the page table into a "
                        "materialized (slots, max_pages*page_size, heads, "
                        "dim) tensor before attending; 'pallas' runs the "
                        "fused paged-attention kernel that walks the page "
                        "table in-kernel (each live K/V page copied once, "
                        "one online-softmax pass) and never materializes "
                        "the gather; 'auto' follows the tuner "
                        "(plan.serve_kv_kernel: pallas targets get the "
                        "kernel).  The two agree to the activation dtype's "
                        "rounding; requires --kv-layout paged")
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a ReplicaRouter over N tuner-split "
                        "engines (1 = single engine)")
    p.add_argument("--route-policy",
                   choices=("round_robin", "least_loaded", "prefix_affinity"),
                   default="least_loaded",
                   help="replica routing policy (with --replicas > 1)")
    p.add_argument("--prefill-chunk", type=int, default=-1,
                   help="prompt tokens ingested per decode tick (chunked "
                        "prefill); -1 = the tuner's plan.serve_prefill_"
                        "chunk, 0 = blocking full-prompt prefill")
    p.add_argument("--trace", choices=TRACES, default="uniform",
                   help="synthetic request mix: uniform (same-length, "
                        "unrelated prompts), zipf (heavy-tailed), "
                        "longprompt (prefill-stall regime), sharedprefix "
                        "(Zipf-clustered shared prompt heads — the mix "
                        "--prefix-cache hits on), repetitive (short "
                        "cyclic prompts, long greedy generations — the "
                        "mix --spec-k pays off on)")
    p.add_argument("--spec-k", default="0",
                   help="speculative decoding: draft tokens per slot per "
                        "verify step (draft-then-verify; 0 = off, 'auto' "
                        "= let the tuner pick from the trace's measured "
                        "n-gram repetitiveness).  Drafts come from a "
                        "deterministic n-gram scan of each request's own "
                        "history; one jitted verify step scores all k+1 "
                        "positions and the longest accepted prefix lands "
                        "in one burst — token streams are bit-identical "
                        "to --spec-k 0")
    p.add_argument("--prefix-cache", action="store_true",
                   help="reuse shared-prefix KV across requests (paged "
                        "layout only): a per-replica cache maps page-"
                        "aligned prompt prefixes to refcounted page runs, "
                        "so a repeat prefix is admitted by page-table "
                        "pointer copies — no chunk steps, no KV writes — "
                        "and only its cold suffix is prefilled; the LRU "
                        "pin budget comes from the tuner's "
                        "plan.serve_prefix_cache_pages and gives way "
                        "under page pressure before any request is "
                        "preempted; token streams are bit-identical "
                        "with the cache on or off")
    p.add_argument("--arrivals", choices=("closed", "poisson", "bursty"),
                   default="closed",
                   help="open-loop arrival process, stamped in VIRTUAL "
                        "STEPS (the deterministic jitted-invocation "
                        "clock, never wall time): 'closed' submits "
                        "everything at t=0 (legacy closed loop); "
                        "'poisson' draws exponential inter-arrival gaps "
                        "of mean --arrival-gap vsteps; 'bursty' "
                        "sinusoidally rate-modulates the Poisson process "
                        "(diurnal-style peaks and troughs).  The router "
                        "admits a request only once the fleet clock "
                        "reaches its arrival; token streams stay "
                        "bit-identical to the closed-loop replay")
    p.add_argument("--arrival-gap", type=float, default=4.0,
                   help="mean inter-arrival gap in virtual steps "
                        "(--arrivals poisson/bursty)")
    p.add_argument("--slo-ttft", type=int, default=0,
                   help="TTFT goodput deadline in virtual steps: only "
                        "requests whose first token lands within the "
                        "deadline count toward goodput_tokens (0 = off, "
                        "-1 = use the tuner's plan.serve_slo_ttft_steps "
                        "napkin value)")
    p.add_argument("--slo-e2e", type=int, default=0,
                   help="end-to-end goodput deadline in virtual steps "
                        "(0 = off, -1 = use the tuner's "
                        "plan.serve_slo_e2e_steps napkin value)")
    p.add_argument("--admission", choices=("queue", "reject"),
                   default="queue",
                   help="router admission control (--replicas > 1): "
                        "'queue' holds every arrival until a replica "
                        "frees up; 'reject' sheds load up front — a "
                        "request whose napkin-predicted TTFT (waited + "
                        "backlog share + own prefill chunks) already "
                        "busts --slo-ttft is rejected with a reason "
                        "instead of queued (needs an SLO)")
    p.add_argument("--autoscale", type=int, default=0,
                   help="fleet autoscaling (--replicas > 1): N = minimum "
                        "serving replicas; the fleet breathes between N "
                        "and --replicas, growing on queue depth or SLO "
                        "headroom and draining idle replicas (drain = "
                        "stop admitting, finish in-flight, park "
                        "dormant).  0 = off (static fleet)")
    p.add_argument("--trace-out", default=None,
                   help="write a Chrome-trace/Perfetto JSON timeline of "
                        "the run to PATH: one 'process' per replica, one "
                        "'thread' per slot (tid 0 = the queue lane), "
                        "spans for every request lifecycle phase "
                        "(queued, prefill chunks, cache attach, decode, "
                        "spec verify, preempt/resume) and instants for "
                        "fleet events (autoscale, rejections, reclaims). "
                        "All timestamps are virtual steps — identical "
                        "runs produce byte-identical files.  Load via "
                        "https://ui.perfetto.dev or chrome://tracing")
    p.add_argument("--metrics-out", default=None,
                   help="write the flat to_metrics() snapshot as JSON to "
                        "PATH after the run (NaN serialized as null); "
                        "works on the single-engine and router paths")
    p.add_argument("--prom-out", default=None,
                   help="write the metrics snapshot in Prometheus text "
                        "exposition format to PATH after the run")
    p.add_argument("--profile-dir", default=None,
                   help="run the drain under jax.profiler.trace, writing "
                        "the profile to DIR: the scheduler's serve.* "
                        "spans and the device's ops (named by "
                        "jax.named_scope) on one clock.  serve.step "
                        "carries the tick's vstep, which lines it up "
                        "with --trace-out.  Load in TensorBoard's "
                        "profile plugin or https://ui.perfetto.dev")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature (0 = greedy)")
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k sampling filter (0 = off)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling: keep the smallest probability "
                        "mass >= p after top-k (1.0 = off)")
    a = p.parse_args(argv)
    spec_k = None if a.spec_k == "auto" else int(a.spec_k)
    serve_main(arch=a.arch, batch=a.batch, prefill_len=a.prefill,
               decode_tokens=a.decode, mode=a.mode, requests=a.requests,
               max_len=a.max_len, kv_layout=a.kv_layout,
               page_size=a.page_size, temperature=a.temperature,
               top_k=a.top_k, top_p=a.top_p, replicas=a.replicas,
               route_policy=a.route_policy,
               prefill_chunk=None if a.prefill_chunk < 0
               else a.prefill_chunk,
               prefix_cache=a.prefix_cache, kv_kernel=a.kv_kernel,
               spec_k=spec_k, trace=a.trace, arrivals=a.arrivals,
               arrival_gap=a.arrival_gap, slo_ttft=a.slo_ttft,
               slo_e2e=a.slo_e2e, admission=a.admission,
               autoscale=a.autoscale, trace_out=a.trace_out,
               metrics_out=a.metrics_out, prom_out=a.prom_out,
               profile_dir=a.profile_dir)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
