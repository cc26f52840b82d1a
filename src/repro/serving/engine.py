"""ServeEngine — the EASEY serving facade (continuous batching).

Glues the existing layers together the same way the training driver does:

    AppSpec(arch, decode shape) + TargetSpec --BuildService--> DeploymentPlan
        (the tuner's serve-mode branch sizes the KV page pool from the HBM
         budget: num_pages x page_size, and records it in the plan/napkin)
    model_for(cfg) + build_prefill_step + build_decode_step_slots
        --> jitted steps (decode donates the pool cache)
    PagedKVCachePool + Scheduler
        --> continuous or gang-scheduled batching

The memory layer is a page pool (``plan.serve_num_pages`` x
``plan.serve_page_size``) with per-slot page tables: slots hold page
lists, so concurrency is bounded by the tokens requests actually hold,
not by worst cases (at the cost of page-pressure preemptions when the
tail bites).

Prompt ingestion runs through the chunk-prefill step
(``build_prefill_chunk_step``), which scatters each chunk's KV straight
into the slot's pages — no intermediate ``(1, s)`` cache.
``prefill_chunk`` picks the grain: the tuner's
``plan.serve_prefill_chunk`` by default (chunks interleave with decode
ticks inside ``Scheduler.step``), or 0 for blocking full-prompt prefill
at admission (the old cadence, kept as the TTFT baseline — both modes
are token-identical by construction).

``prefix_cache=True`` attaches a shared-prefix KV cache
(``serving/prefix_cache.PrefixCache``) to every pool this engine builds:
admissions whose prompt prefix is already resident reuse the cached page
run by pointer copy and prefill only the cold suffix.  The tuner budgets
the cache's LRU pin cap (``plan.serve_prefix_cache_pages``) out of the
same page pool.  Cached and cache-off runs are token-
identical by construction — the cache only changes *where* prefix KV
comes from, never its bits.

``kv_kernel`` selects the paged decode attention implementation:

* ``"gather"`` — read K/V back *through* the page table into a
  materialized ``(slots, max_pages*page_size, K, dh)`` tensor, then
  attend (the reference path).
* ``"pallas"`` — the fused Pallas paged-attention kernel
  (``kernels/paged_attention.py``): the page table is walked inside the
  kernel, each slot's live K/V pages are copied once, in double-buffered
  blocks, with one online-softmax pass, and the materialized gather never
  hits HBM.
* ``"auto"`` (default) — follow the tuner (``plan.serve_kv_kernel``:
  pallas targets get the kernel, reference targets the gather).

The kernel runs compiled on a TPU and in the Pallas interpreter on any
other platform (``kernels.ops.interpret_mode``); the engine logs its
target, platform, kernel and interpret mode when it is built.
``target=None`` (default) is the one-chip target of the first attached
device (``core.target.serve_target``): serving runs on one chip.

The two implementations agree to the activation dtype's rounding, not
bit for bit: the kernel rounds its unnormalised probabilities where the
gather path rounds the normalised ones.  In float32 their token streams
are identical; in bfloat16 the kernel is held to the gather oracle
within 2e-2 (tests/test_kernels_paged.py) and its decode logits to the
gather path's distance from float32 ones (tests/test_serving_paged.py),
so a near-tie may pick another token.

``launch/serve.py`` is a thin CLI over this class; the serving benchmark
drives both policies through engines that share the request traces, so
every comparison is apples-to-apples.

``replicas`` > 1 declares this engine one of N co-resident replicas
behind a ``ReplicaRouter``: the tuner splits the HBM budget N ways and
every pool size above becomes a per-replica figure (the plan's napkin
additionally quotes the fleet-aggregate ``serve_fleet_capacity``).

``spec_k`` turns on draft-then-verify speculative decoding: every decode
tick drafts k tokens per slot (``serving/spec.NGramDrafter`` by default —
longest-suffix n-gram over the slot's own prompt + generated history; any
object with ``draft(history, k)`` plugs in via ``drafter=``, the hook a
small ``configs/`` drafter model drops into), scores all k+1 positions in
ONE jitted verify step, and accepts the longest draft prefix matching the
sequential sampler's own ``(rid, step)`` draws — so speculative token
streams are **bit-identical** to ``spec_k=0`` while a tick can emit up to
k+1 tokens per slot (with ``kv_kernel="pallas"`` the verify step still
reads through the gather path, so there the two agree to the activation
dtype's rounding, as the kernel and the gather path do).  Accepted bursts are charged against pages with the
junk-page-0 overwrite guard, so a burst can never scribble into a
prefix-shared page.  ``spec_k=None`` defers to the tuner
(``plan.serve_spec_k``, picked from the trace's repetitiveness — see
``repetitiveness=``); 0 disables.  Typical usage::

    eng = ServeEngine(arch="picolm-4-smoke", spec_k=4)
    stats = eng.run(repetitive_trace(32, eng.cfg.vocab_size))
    stats.accepted_per_verify     # tokens emitted per verify step (> 1
    stats.spec_accepted_tokens    #  when drafts are being accepted)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.appspec import AppSpec
from repro.core.build import BuildService
from repro.core.target import serve_target
from repro.kernels.ops import interpret_mode
from repro.models.params import init_params
from repro.models.transformer import model_for
from repro.serving.pool import PagedKVCachePool
from repro.serving.sampling import make_sampler
from repro.serving.scheduler import Scheduler, ServeStats
from repro.training.steps import (build_decode_step_slots,
                                  build_prefill_chunk_step,
                                  build_prefill_step,
                                  build_verify_step_slots)

SERVABLE_FAMILIES = ("dense", "moe")
KV_KERNELS = ("auto", "gather", "pallas")


class ServeEngine:
    """One model + one KV pool + jitted steps; runs request traces."""

    def __init__(self, arch: str = "deepseek-7b-smoke",
                 target: str | None = None, num_slots: int = 8,
                 max_len: int = 128, seed: int = 0,
                 eos_id: int | None = None, kv_layout: str = "paged",
                 page_size: int = 0, num_pages: int = 0,
                 replicas: int = 1, prefill_chunk: int | None = None,
                 prefix_cache: bool = False, kv_kernel: str = "auto",
                 spec_k: int | None = 0, drafter=None,
                 repetitiveness: float = 0.0, log=print):
        # the pool is paged; the keyword stays for callers written when
        # there was a second layout, which still pass it
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout {kv_layout!r}: the serving pool is paged")
        if kv_kernel not in KV_KERNELS:
            raise ValueError(f"kv_kernel {kv_kernel!r} not in {KV_KERNELS}")
        if replicas < 1:
            raise ValueError(f"replicas {replicas} < 1")
        # `replicas` tells the tuner how many co-resident engines split the
        # HBM budget (ReplicaRouter fleets); num_slots stays the *per
        # replica* ask, so the fleet-wide batch is num_slots x replicas
        if spec_k is not None and spec_k < 0:
            raise ValueError(f"spec_k {spec_k} < 0")
        if not 0.0 <= repetitiveness <= 1.0:
            raise ValueError(f"repetitiveness {repetitiveness} not in [0, 1]")
        app = AppSpec(arch=arch, shape="decode_32k",
                      shape_overrides={"seq_len": max_len,
                                       "global_batch": num_slots * replicas,
                                       "serve_replicas": replicas,
                                       "serve_repetitiveness": repetitiveness},
                      run="serve --engine continuous")
        cfg = app.model_config
        if cfg.family not in SERVABLE_FAMILIES:
            raise NotImplementedError(
                f"ServeEngine needs a slot-indexable attention KV cache; "
                f"family {cfg.family!r} is served by the legacy static path")
        if cfg.window:
            raise NotImplementedError(
                "slot-wise decode does not support sliding-window attention "
                "yet (the pool would attend the full history)")
        tgt = serve_target(target)
        result = BuildService().build(app, tgt, lower=False)
        self.plan = result.plan
        self.replicas = replicas
        self.max_len = self.plan.serve_max_len or max_len
        # the page pool, not the slot count, is the HBM reservation: slots
        # are page-table rows, so the engine keeps the requested
        # concurrency (capped only by one-page-per-active-request)
        self.page_size = page_size or self.plan.serve_page_size or 16
        if num_pages:
            self.num_pages = num_pages
        elif self.plan.serve_num_pages and \
                self.page_size == self.plan.serve_page_size:
            self.num_pages = self.plan.serve_num_pages
        elif self.plan.serve_num_pages:
            # tuner sized the pool for its own page size — carry the
            # *token* budget over to the requested page size
            tokens = (self.plan.serve_num_pages - 1) * \
                self.plan.serve_page_size
            self.num_pages = max(tokens // self.page_size, 1) + 1
        else:
            self.num_pages = 0
        usable = (self.num_pages - 1) if self.num_pages else num_slots
        self.num_slots = max(1, min(num_slots, usable))
        if self.num_slots < num_slots:
            log(f"[serve] pool capped by page budget: {num_slots} -> "
                f"{self.num_slots} slots (1 page per active request)")
        self.cfg = cfg
        self.model = model_for(cfg, remat="none")
        self.mesh = None if tgt.num_chips == 1 else result.mesh
        self.eos_id = eos_id
        self.seed = seed
        self.log = log
        # shared-prefix KV cache: the tuner carves an LRU pin budget out
        # of the same page pool; default off so cache-off baselines (and
        # every pre-cache benchmark cell) are untouched.
        # The plan's quota is a page count for the PLAN's pool — make_pool
        # re-caps it against the pool actually built, so an explicit
        # --num-pages/--page-size override can never void the ~1/4 bound.
        self.prefix_cache = prefix_cache
        self.prefix_cache_pages = self.plan.serve_prefix_cache_pages
        # prompt-ingestion grain: None -> the tuner's chunk size; 0 ->
        # blocking full-prompt prefill; >0 -> explicit chunk tokens.
        # chunk_unit prices blocking prefills on the virtual TTFT clock
        # in the SAME chunk-equivalents, whatever mode runs.
        self.chunk_unit = self.plan.serve_prefill_chunk or 16
        self.prefill_chunk = self.chunk_unit if prefill_chunk is None \
            else prefill_chunk
        self.params = init_params(self.model.param_table(),
                                  jax.random.PRNGKey(seed))
        self.sampler = make_sampler(seed)
        prefill = build_prefill_step(self.model, self.mesh)
        self._prefill = jax.jit(prefill)
        # "auto" follows the tuner's call for this target; the plan field
        # is only "" for non-serve shapes, so default to gather
        self.kv_kernel = kv_kernel if kv_kernel != "auto" \
            else (self.plan.serve_kv_kernel or "gather")
        decode = build_decode_step_slots(
            self.model, self.mesh, use_kernel=(self.kv_kernel == "pallas"))
        chunk = build_prefill_chunk_step(self.model, self.mesh)
        verify = build_verify_step_slots(self.model, self.mesh)
        log(f"[serve] target={tgt.name} platform={jax.default_backend()} "
            f"layers={cfg.num_layers} kv_kernel={self.kv_kernel} interpret="
            f"{interpret_mode() if self.kv_kernel == 'pallas' else None}")
        self._decode = jax.jit(decode, donate_argnums=(1,))
        # kv_bound (arg 6) is static: it sizes the chunk's KV read-back,
        # so the chunk jit cache is (chunk buckets) x (bound buckets)
        self._chunk = jax.jit(chunk, donate_argnums=(1,),
                              static_argnums=(6,))
        # speculative verify step: jit is lazy, so building it costs
        # nothing until spec_k > 0 actually drives a verify tick
        self._verify = jax.jit(verify, donate_argnums=(1,))
        # spec_k=None defers to the tuner's pick for this trace shape
        # (plan.serve_spec_k, from the serve_repetitiveness hint); the
        # Pallas kernel still serves the s=1 ticks — verify bursts read
        # through the gather path inside the step
        self.spec_k = self.plan.serve_spec_k if spec_k is None else spec_k
        self.drafter = drafter

    # -- step wrappers bound to the params ---------------------------------
    def prefill_fn(self, tokens: jax.Array, last: int | None = None):
        batch = {"tokens": tokens}
        if last is not None:
            batch["last"] = jnp.int32(last)
        return self._prefill(self.params, batch)

    def decode_fn(self, cache, tokens, active, *extras):
        return self._decode(self.params, cache, tokens, active, *extras)

    def chunk_fn(self, cache, tokens, slot, offset, n_valid, *extras):
        """Prefill one prompt chunk straight into the pool cache (donated)."""
        return self._chunk(self.params, cache, tokens, slot, offset,
                           n_valid, *extras)

    def verify_fn(self, cache, tokens, active, *extras):
        """Score a (num_slots, k+1) speculative batch; logits at every
        position (cache donated; index stays host-authoritative)."""
        return self._verify(self.params, cache, tokens, active, *extras)

    # -- driving -----------------------------------------------------------
    def make_pool(self, prefix_cache: bool | None = None):
        """A fresh pool (and, when enabled, a fresh shared-prefix cache
        attached to it — per pool, so per replica under a router).
        ``prefix_cache`` overrides the engine default for this pool."""
        use_cache = self.prefix_cache if prefix_cache is None \
            else prefix_cache
        pool = PagedKVCachePool(self.model, self.num_slots, self.max_len,
                                page_size=self.page_size,
                                num_pages=self.num_pages)
        if use_cache:
            from repro.core.tuning import prefix_cache_quota
            from repro.serving.prefix_cache import PrefixCache
            # the tuner's quota, but never more than ~1/4 of the pool that
            # actually got built (it may be smaller than the plan's when
            # --num-pages/--page-size override the tuner)
            cap = prefix_cache_quota(pool.num_pages)
            budget = min(self.prefix_cache_pages or cap, cap)
            PrefixCache(pool, max_pages=max(budget, 1))
        return pool

    def run(self, requests, policy: str = "continuous",
            prefill_chunk: int | None = None,
            prefix_cache: bool | None = None,
            spec_k: int | None = None,
            slo_ttft_steps: int = 0,
            slo_e2e_steps: int = 0,
            tracer=None) -> ServeStats:
        """Drain `requests` under `policy` ('continuous' | 'static').

        A fresh pool per run keeps back-to-back policy comparisons honest
        (same cold cache state; jitted steps stay warm across runs).
        ``prefill_chunk`` overrides the engine's ingestion grain for this
        run (0 = blocking full-prompt prefill); ``prefix_cache`` toggles
        the shared-prefix KV cache for this run — cached and cache-off
        runs share every jitted step, so either comparison is free.
        ``spec_k`` overrides the engine's speculative draft length for
        this run (0 = plain one-token decode) — spec-on and spec-off runs
        also share every jitted step, and their token streams are
        bit-identical by construction.
        ``slo_ttft_steps`` / ``slo_e2e_steps`` set the virtual-step
        deadlines ``ServeStats.goodput_tokens`` is judged by (0 = unset;
        the tuner's suggestions live in ``plan.serve_slo_ttft_steps`` /
        ``plan.serve_slo_e2e_steps``).  Requests whose ``arrival_vstep``
        is set are admitted open-loop: only once the virtual clock
        reaches their arrival.
        ``tracer`` (a ``serving.telemetry.Tracer``) records per-request
        spans and ring events on the virtual clock — pure host-side
        bookkeeping behind None-guards, so tracing on/off cannot change
        a single token.
        """
        chunk = self.prefill_chunk if prefill_chunk is None else prefill_chunk
        k = self.spec_k if spec_k is None else spec_k
        sched = Scheduler(self.make_pool(prefix_cache=prefix_cache),
                          self.prefill_fn, self.decode_fn,
                          eos_id=self.eos_id, policy=policy,
                          sampler=self.sampler, chunk_step_fn=self.chunk_fn,
                          prefill_chunk=chunk,
                          prefill_chunk_unit=self.chunk_unit,
                          verify_fn=self.verify_fn if k else None,
                          spec_k=k, drafter=self.drafter,
                          vocab_size=self.cfg.vocab_size,
                          slo_ttft_steps=slo_ttft_steps,
                          slo_e2e_steps=slo_e2e_steps,
                          tracer=tracer)
        stats = sched.run(list(requests))
        self.log(f"[serve:{policy}] {stats.summary()}")
        return stats
