"""AutoTuner — EASEY's `###includelocalmpi###` mechanism for TPU (§2.1).

Given (ModelConfig, ShapeConfig, TargetSpec) it derives a DeploymentPlan by
explicit napkin math over the target's memory/compute budget:

* parameter + optimizer bytes per chip  -> optimizer variant (fp32 vs int8)
* activation bytes per microbatch       -> microbatch count + remat policy
* gradient accumulation dtype           -> fp32 unless HBM-bound
* kernel library                        -> pallas on TPU, reference on CPU
* sharding fallbacks                    -> recorded for the tuning report

Every decision lands in the DeploymentPlan (shipped in the package
manifest), so a deployment is as auditable as the paper's generated batch
files.
"""

from __future__ import annotations

import math

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.plan import DeploymentPlan
from repro.core.target import TargetSpec

# paged serve layout: tokens per KV page, and the expected fraction of
# max_len a request actually uses (heavy-tailed traces — the capacity
# quote in the napkin is per *expected* tokens, not per worst case)
SERVE_PAGE_SIZE = 16
SERVE_EXPECTED_LEN_FRACTION = 0.25
# speculative decoding: below this trace repetitiveness the n-gram
# drafter's expected accepted-tokens/verify (~1/(1-r)) does not cover the
# verify step's (k+1)-wide compute, so the tuner keeps spec off
SPEC_MIN_REPETITIVENESS = 0.35
SPEC_MAX_K = 8
# Pallas kernels budget this fraction of the target's per-core VMEM for
# block + scratch residency; the remainder covers compiler-managed
# spills and semaphores.  analysis/lint's vmem-budget rule enforces it
# statically (and mirrors the fraction for JAX-less environments —
# tests pin the two together).
VMEM_BUDGET_FRACTION = 0.9


def vmem_budget_bytes(target: TargetSpec) -> float:
    """Static VMEM byte budget a single Pallas kernel may plan for."""
    return VMEM_BUDGET_FRACTION * target.vmem_bytes


# SLO deadlines the tuner suggests, on the virtual step clock: TTFT gets
# a multiple of the expected prefill stall (queue wait + ingestion both
# have to fit under it), e2e adds a per-token decode allowance on top
SERVE_SLO_TTFT_STALL_MULT = 4
SERVE_SLO_E2E_STEPS_PER_TOKEN = 2


def ttft_napkin_steps(prompt_len: int, chunk_unit: int,
                      backlog_chunks: int = 0,
                      waited_steps: int = 0) -> int:
    """Predicted time-to-first-token, in virtual steps — the napkin the
    router's SLO admission consults before queueing a request.

    The prediction is the steps already waited, plus the fleet's pending
    prefill backlog (in chunk-equivalents — the share one replica would
    have to chew through first), plus the request's own prompt priced at
    ``ceil(prompt_len / chunk_unit)`` chunk steps.  Chunk-equivalents are
    the same unit the virtual clock prices blocking prefills in, so the
    prediction and the measured ``ttft_steps`` are directly comparable.
    """
    own = -(-max(int(prompt_len), 1) // max(int(chunk_unit), 1))
    return int(waited_steps) + int(backlog_chunks) + own


def spec_k_for(repetitiveness: float) -> int:
    """Draft length the tuner picks for a trace's repetitiveness r.

    r proxies the per-draft accept probability, so a k-draft verify step
    emits E(k) = (1 - r^{k+1})/(1 - r) tokens in expectation.  E(k) is
    increasing but saturating in k; each extra draft costs verify compute
    whether or not it is accepted, so k stops where the marginal token
    gain r^k drops below ~0.1 (diminishing returns), capped at
    SPEC_MAX_K.  r below SPEC_MIN_REPETITIVENESS turns spec off (0).
    """
    r = min(max(float(repetitiveness), 0.0), 0.99)
    if r < SPEC_MIN_REPETITIVENESS:
        return 0
    k = 1
    while k < SPEC_MAX_K and r ** (k + 1) >= 0.1:
        k += 1
    return k


def param_count_estimate(cfg: ModelConfig) -> int:
    """Exact parameter count, straight from the model's ParamDef table
    (metadata only — no allocation)."""
    if cfg.family == "stencil":
        return 0
    from repro.models.params import param_count
    from repro.models.transformer import model_for
    return param_count(model_for(cfg).param_table())


def kv_bytes_per_token(cfg: ModelConfig, stored: bool = False) -> int:
    """HBM bytes one KV-cache token costs (k+v, all layers) — the unit the
    serve-mode budget is denominated in.  Single source of truth for the
    tuner, the serving benchmark, and the budget-target tests.  Latent
    attention caches one row of ``kv_lora_rank + qk_rope_head_dim`` per
    layer; ``stored`` counts it as the paged pool stores it, padded to
    128-lane tiles (``models/mla.latent_lanes``)."""
    import jax.numpy as jnp
    item = jnp.dtype(cfg.activation_dtype).itemsize
    if cfg.kv_lora_rank:
        from repro.models.mla import latent_lanes
        width = latent_lanes(cfg) if stored else \
            cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return cfg.num_layers * width * item
    per = 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * item
    if cfg.family == "encdec":
        per *= 2  # self- and cross-attention caches
    return per


def prefix_cache_quota(num_pages: int) -> int:
    """LRU pin cap for the shared-prefix KV cache: ~1/4 of the
    allocatable page pool, so hot prefixes can never squeeze live
    requests below 3/4 of their pages.  Single source of truth for the
    tuner and for engines built without a plan-derived value."""
    return max((num_pages - 1) // 4, 1) if num_pages else 0


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: only top-k experts active).
    The weights counted are those held here (``param_count_estimate``);
    where a share of the experts is held, serving computes each held
    expert for every token, so all of them are active."""
    total = param_count_estimate(cfg)
    if cfg.family != "moe" or cfg.experts_held:
        return total
    gated = 3 if cfg.activation in ("silu", "geglu") else 2
    per_expert = gated * cfg.d_model * (cfg.moe_d_ff or cfg.d_ff)
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    inactive = (cfg.num_experts - cfg.experts_per_token) * per_expert \
        * moe_layers
    return total - inactive


def tune(cfg: ModelConfig, shape: ShapeConfig, target: TargetSpec,
         overrides: dict | None = None) -> DeploymentPlan:
    chips = target.num_chips
    plan = DeploymentPlan(
        arch=cfg.name, shape=shape.name, target=target.name,
        mesh_shape=target.mesh_shape, mesh_axes=target.mesh_axes,
        kernels=target.kernels)

    P = param_count_estimate(cfg)
    param_bytes = 2 * P  # bf16
    plan.napkin["params"] = f"{P/1e9:.2f}B"
    plan.napkin["param_bytes_per_chip"] = f"{param_bytes/chips/1e9:.3f} GB"

    if shape.kind == "train":
        budget = 0.85 * target.hbm_bytes
        fixed = param_bytes / chips
        grad_fp32 = 4 * P / chips
        opt_fp32 = 8 * P / chips
        plan.napkin["opt_fp32_per_chip"] = f"{opt_fp32/1e9:.2f} GB"
        # minimum activation footprint (full remat, max microbatches) —
        # used to decide the optimizer variant up front
        axes0 = dict(zip(target.mesh_axes, target.mesh_shape))
        bs0 = axes0.get("pod", 1) * axes0.get("data", 1)
        mm0 = max(int(shape.global_batch // bs0), 1)
        w0 = cfg.d_model if cfg.family not in ("ssm_xlstm", "hybrid_mamba") \
            else (cfg.ssm_expand + 1) * cfg.d_model
        L0 = cfg.num_layers + cfg.num_encoder_layers
        min_act = 3.5 * (shape.global_batch * shape.seq_len / bs0 / mm0) * \
            L0 * w0 * 2
        if fixed + opt_fp32 + grad_fp32 + min_act > budget:
            plan.optimizer = "adamw8bit"
            opt_bytes = (2 * P + 8 * max(P // 128, 1)) / chips
            plan.notes.append(
                "fp32 Adam moments + activations exceed HBM -> int8 moments")
        else:
            plan.optimizer = "adamw"
            opt_bytes = opt_fp32
        # --- grad accumulation dtype (may be escalated by the ladder) ---
        if fixed + opt_bytes + grad_fp32 > budget:
            plan.grad_accum_dtype = "bfloat16"
            grad_bytes = 2 * P / chips
            plan.notes.append("fp32 grad accumulator exceeds budget -> bf16")
        else:
            grad_bytes = grad_fp32
        headroom = budget - fixed - opt_bytes - grad_bytes
        plan.napkin["headroom_for_activations"] = f"{headroom/1e9:.2f} GB"
        headroom_bf16_grads = budget - fixed - opt_bytes - 2 * P / chips

        # --- microbatches / remat / SP escalation ladder (perf iter I2) ---
        # Empirical calibration from the dry-run memory_analysis (see
        # EXPERIMENTS.md §Perf): XLA temp ~= FACTOR x (stacked layer inputs
        # per microbatch per device), FACTOR ~6 under 'dots' remat, ~3.5
        # under full remat (recompute working set + loop double-buffering).
        axes = dict(zip(target.mesh_axes, target.mesh_shape))
        batch_shards = axes.get("pod", 1) * axes.get("data", 1)
        model_size = axes.get("model", 1)
        L_eff = cfg.num_layers + cfg.num_encoder_layers
        tokens_local = shape.global_batch * shape.seq_len / batch_shards
        per_layer_width = cfg.d_model
        if cfg.family in ("ssm_xlstm", "hybrid_mamba"):
            per_layer_width = (cfg.ssm_expand + 1) * cfg.d_model

        def est_temp(micro, factor, seq_shards=1):
            saved = (tokens_local / micro) * L_eff * per_layer_width * 2
            return factor * saved / seq_shards

        max_micro = max(int(shape.global_batch // batch_shards), 1)
        # escalation ladder, cheapest knob first: each config is
        # (remat, factor, seq_parallel, bf16_grads).  Microbatches are the
        # inner loop (fewest first — per-micro FSDP weight re-gathers make
        # micro the most expensive collective knob, measured in it1/it2).
        # SP is skipped for MoE (I2b: expert dispatch reshards per chunk).
        ladder = [("dots", 6.0, False, False), ("full", 3.5, False, False),
                  ("dots", 6.0, False, True), ("full", 3.5, False, True)]
        if cfg.family != "moe":
            ladder += [("dots", 6.0, True, False), ("full", 3.5, True, False),
                       ("dots", 6.0, True, True), ("full", 3.5, True, True)]
        chosen = None
        for remat, factor, sp, bf16g in ladder:
            room = headroom_bf16_grads if bf16g else headroom
            shards = model_size if sp else 1
            micro = 1
            while micro <= max_micro:
                if shape.global_batch % micro == 0 and \
                        est_temp(micro, factor, shards) <= room:
                    chosen = (remat, micro, sp, bf16g)
                    break
                micro *= 2
            if chosen:
                break
        if not chosen:
            chosen = ("full", max_micro, cfg.family != "moe", True)
            plan.notes.append("I2: memory estimate exceeds HBM even at the "
                              "top of the escalation ladder")
        plan.remat_policy, plan.microbatches, plan.sequence_parallel, bf16g = chosen
        if bf16g and plan.grad_accum_dtype != "bfloat16":
            plan.grad_accum_dtype = "bfloat16"
            plan.notes.append("I2: bf16 grad accumulation (ladder escalation)")
        if chosen[2]:
            plan.notes.append("I2: sequence-parallel activations "
                              "(saved tensors shard over the model axis)")
        factor = 6.0 if plan.remat_policy == "dots" else 3.5
        shards = model_size if plan.sequence_parallel else 1
        plan.napkin["est_temp_per_chip"] = (
            f"{est_temp(plan.microbatches, factor, shards) / 1e9:.2f} GB")
    else:
        plan.microbatches = 1
        plan.remat_policy = "none"
        # decode/prefill memory: params + kv cache
        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            kv_per_token = kv_bytes_per_token(cfg, stored=True)
            kv = kv_per_token * shape.global_batch * shape.seq_len
            plan.napkin["kv_cache_per_chip"] = f"{kv/chips/1e9:.3f} GB"
            # --- serve-mode KV pool sizing ---------------------------------
            # The continuous-batching engine asks for (slots x max_len);
            # the requested batch is honoured only while params + pool fit
            # the HBM budget, otherwise the pool is capped — the serving
            # analogue of the training escalation ladder.  Both KV layouts
            # are sized and recorded: the contiguous pool reserves
            # worst-case (max_len) per admitted request, the paged pool
            # turns the same budget into *pages* so capacity is measured
            # in expected tokens instead of worst cases.
            #
            # With `shape.serve_replicas` > 1 the KV budget is split evenly
            # across N co-resident engines (params are shared weights, so
            # only the pools divide), every slot/page count below is *per
            # replica*, and the napkin quotes the fleet-aggregate capacity
            # — the quantity the ReplicaRouter balances.
            replicas = max(int(getattr(shape, "serve_replicas", 1) or 1), 1)
            plan.serve_replicas = replicas
            budget = (0.85 * target.hbm_bytes - param_bytes / chips) / replicas
            replica_batch = max(math.ceil(shape.global_batch / replicas), 1)
            per_slot = kv_per_token * shape.seq_len / chips
            cap = max(int(budget // per_slot), 1) if per_slot > 0 else \
                replica_batch
            plan.serve_max_len = shape.seq_len
            plan.serve_slots = max(1, min(replica_batch, cap))
            per = " per replica" if replicas > 1 else ""
            plan.napkin["serve_pool"] = (
                f"{plan.serve_slots} slots x {shape.seq_len} "
                f"({plan.serve_slots * per_slot / 1e9:.3f} GB/chip{per})")
            if plan.serve_slots < replica_batch:
                plan.notes.append(
                    f"serve: requested {replica_batch} slots{per} exceed the "
                    f"HBM budget -> pool capped at {plan.serve_slots}")
            # paged layout: same budget buys a page pool.  Pages beyond the
            # requested batch's worst case are pointless, so the pool is
            # capped there; capacity is then quoted against the *expected*
            # request length (heavy-tailed traces use ~1/4 of max_len on
            # average), not against max_len.
            page_size = min(SERVE_PAGE_SIZE, shape.seq_len)
            page_bytes = kv_per_token * page_size / chips
            worst_pages = replica_batch * \
                math.ceil(shape.seq_len / page_size) + 1  # + junk page 0
            budget_pages = max(int(budget // page_bytes), 2) \
                if page_bytes > 0 else worst_pages
            plan.serve_page_size = page_size
            plan.serve_num_pages = min(budget_pages, worst_pages)
            expected_len = max(
                int(shape.seq_len * SERVE_EXPECTED_LEN_FRACTION), 1)
            usable_tokens = (plan.serve_num_pages - 1) * page_size
            paged_reqs = max(usable_tokens // expected_len, 1)
            plan.napkin["kv_pages"] = plan.serve_num_pages
            plan.napkin["page_size"] = page_size
            plan.napkin["serve_pool_paged"] = (
                f"{plan.serve_num_pages} pages x {page_size} "
                f"({plan.serve_num_pages * page_bytes / 1e9:.3f} GB/chip{per})")
            delta = paged_reqs / max(plan.serve_slots, 1) - 1.0
            plan.napkin["serve_capacity_delta"] = (
                f"contiguous {plan.serve_slots} worst-case reqs vs paged "
                f"~{paged_reqs} expected-len({expected_len}) reqs "
                f"({delta:+.0%}){per}")
            # --- chunked-prefill grain + TTFT napkin -----------------------
            # Prompt ingestion interleaves with decode ticks; the chunk is
            # sized so one chunk's FLOPs fit inside one decode tick's
            # budget (decode is bandwidth-bound on the weights, so a tick
            # costs ~max(param-read time, batch compute) — prefill chunks
            # ride in that shadow without stretching the tick).  Bucketed
            # to a power of two so the chunk jit cache stays small.
            flops_tok = 2 * active_param_count(cfg)
            t_tick = max(param_bytes / chips / target.hbm_bw,
                         plan.serve_slots * flops_tok / target.peak_flops)
            c_raw = t_tick * target.peak_flops / max(flops_tok, 1)
            chunk = 8
            while chunk * 2 <= min(c_raw, 128, shape.seq_len):
                chunk *= 2
            plan.serve_prefill_chunk = chunk
            stall = -(-expected_len // chunk)     # chunk-equivalent ticks
            plan.napkin["serve_prefill_chunk"] = chunk
            plan.napkin["ttft_estimate"] = (
                f"expected {expected_len}-token prompt = {stall} chunk(s) "
                f"x ~{t_tick*1e3:.2f} ms/tick ≈ {stall*t_tick*1e3:.1f} ms "
                f"to first token; chunked ingest overlaps those ticks "
                f"with decode, blocking stalls the loop for all of them")
            # --- SLO deadlines (virtual step clock) ------------------------
            # The same stall estimate, held to a deadline: TTFT gets a
            # SERVE_SLO_TTFT_STALL_MULT x headroom over the expected
            # prefill (queue wait + ingestion must both fit), e2e adds
            # SERVE_SLO_E2E_STEPS_PER_TOKEN vsteps per expected generated
            # token on top.  Virtual steps, never wall-clock — the router
            # judges goodput and rejects hopeless admissions against
            # these (launch/serve.py --slo-ttft/-e2e -1 = use the plan's).
            plan.serve_slo_ttft_steps = \
                SERVE_SLO_TTFT_STALL_MULT * (stall + 1)
            plan.serve_slo_e2e_steps = plan.serve_slo_ttft_steps + \
                SERVE_SLO_E2E_STEPS_PER_TOKEN * expected_len
            plan.napkin["serve_slo"] = (
                f"ttft <= {plan.serve_slo_ttft_steps} vsteps "
                f"({SERVE_SLO_TTFT_STALL_MULT}x expected prefill stall), "
                f"e2e <= {plan.serve_slo_e2e_steps} vsteps "
                f"(+{SERVE_SLO_E2E_STEPS_PER_TOKEN}/token over "
                f"{expected_len} expected tokens)")
            # --- shared-prefix KV cache budget -----------------------------
            # The cache pins already-resident page runs (LRU) so repeat
            # prefixes re-prefill nothing; it spends no new HBM — the cap
            # carves a pin quota out of the page pool above so hot
            # prefixes can't squeeze live requests below ~3/4 of the
            # pool.  Savings quote: a hit on an expected-length prompt
            # skips every fully-covered page's worth of chunk steps.
            cache_pages = prefix_cache_quota(plan.serve_num_pages)
            plan.serve_prefix_cache_pages = cache_pages
            if cache_pages:
                # probe caps a hit at (len-1)//page_size pages (>= 1
                # suffix token always re-prefills, its logits seed the
                # first sample), so a page-aligned prompt still pays one
                # page — quote that, not a zero-cost hit
                aligned = (expected_len - 1) // page_size * page_size
                saved = stall - -(-(expected_len - aligned) // chunk)
                plan.napkin["serve_prefix_cache"] = (
                    f"{cache_pages} pages ({cache_pages * page_size} "
                    f"tokens) LRU-pinnable for shared prefixes; a hit on "
                    f"an expected {expected_len}-token prompt re-prefills "
                    f"{expected_len - aligned} instead of {expected_len} "
                    f"tokens (~{saved} of {stall} chunk steps saved)")
            # --- paged decode attention kernel -----------------------------
            # Pallas targets get the fused paged-attention kernel (page
            # table walked in-kernel); reference targets keep the
            # gather-then-attend read.  The napkin quotes what the gather
            # materializes per decode tick: the full worst-case
            # (slots, max_pages*page_size, K, dh) K/V read, vs the fused
            # kernel touching only pages each slot actually holds.
            plan.serve_kv_kernel = \
                "pallas" if target.kernels == "pallas" else "gather"
            slot_cap = math.ceil(shape.seq_len / page_size) * page_size
            gather_bytes = kv_per_token * plan.serve_slots * slot_cap / chips
            fused_bytes_est = \
                kv_per_token * plan.serve_slots * expected_len / chips
            plan.napkin["serve_kv_kernel"] = (
                f"{plan.serve_kv_kernel}: gather materializes "
                f"{gather_bytes/1e9:.3f} GB/chip of K/V per decode tick "
                f"(worst-case page runs); fused pallas streams only held "
                f"pages (~{fused_bytes_est/1e9:.3f} GB/chip at expected "
                f"lengths)")
            # --- speculative decoding (draft-then-verify) ------------------
            # The trace's repetitiveness r (n-gram self-overlap in [0, 1],
            # measured by serving/trace.trace_repetitiveness and passed in
            # as a shape hint) doubles as the napkin's per-draft accept
            # probability: a k-draft verify step then emits
            # E(k) = 1 + r + r^2 + ... + r^k = (1 - r^{k+1}) / (1 - r)
            # tokens in expectation for ONE jitted call.  Verify compute
            # grows ~(k+1)x but decode is bandwidth-bound on the weights,
            # so E(k) > 1 is (napkin-)free throughput; below the
            # break-even repetitiveness the drafts just miss and the plan
            # keeps spec off.
            rep = float(getattr(shape, "serve_repetitiveness", 0.0) or 0.0)
            plan.serve_spec_k = spec_k_for(rep)
            if plan.serve_spec_k:
                k = plan.serve_spec_k
                est = (1.0 - rep ** (k + 1)) / (1.0 - rep)
                plan.napkin["serve_spec"] = (
                    f"spec_k={k} at repetitiveness {rep:.2f}: expected "
                    f"~{est:.2f} accepted tokens/verify step "
                    f"(1 guaranteed + drafts while they match)")
            elif rep:
                plan.napkin["serve_spec"] = (
                    f"spec off: repetitiveness {rep:.2f} < "
                    f"{SPEC_MIN_REPETITIVENESS} — expected accepted "
                    f"tokens/verify ~{1.0 / (1.0 - min(rep, 0.99)):.2f} "
                    f"does not cover the verify overhead")
            # fleet capacity: what N replicas hold together, in tokens —
            # the quantity a router's least-loaded policy balances
            fleet_tokens = replicas * usable_tokens
            plan.napkin["serve_fleet_tokens"] = fleet_tokens
            plan.napkin["serve_fleet_capacity"] = (
                f"{replicas} replica(s) x {usable_tokens} paged tokens = "
                f"{fleet_tokens} tokens | {replicas} x {plan.serve_slots} "
                f"contiguous slots = {replicas * plan.serve_slots} "
                f"worst-case reqs")

    # --- long-context sequence parallelism ---
    if shape.kind != "train" and shape.seq_len >= 131072 and \
            shape.global_batch < dict(zip(target.mesh_axes, target.mesh_shape)).get("data", 1):
        plan.sequence_parallel = True
        plan.notes.append("batch smaller than data axis at long context -> "
                          "sequence-parallel activations")

    if overrides:
        for k, v in overrides.items():
            setattr(plan, k, v)
    return plan
