"""Request scheduler: admission, in-flight batching, eviction, preemption.

Two policies over the same paged KV pool and jitted steps:

* ``continuous`` — between decode steps, every freed slot is immediately
  re-prefilled from the queue (continuous batching / in-flight batching).
* ``static`` — gang scheduling: admit a full batch, drain it until the
  *last* request finishes, then admit the next batch.  This is the old
  ``launch/serve.py`` behaviour, kept as the benchmark baseline.

The scheduler admits through ``pool.can_admit`` (free *pages*, with
headroom reserved for in-flight requests about to cross a page
boundary), grows slots before each decode step via
``pool.prepare_decode``, and — when the page pool is starved mid-decode —
**preempts** the youngest in-flight request: its slot and pages are
freed and it is re-queued at the front.  A preempted request is resumed
by re-prefilling its prompt plus everything it already generated, which
reproduces its KV state exactly, so preemption never changes the token
stream (greedy, and sampled too: the sampler keys on request id and
generation step, not on slot or time).

The preemption victim is the request with the **youngest admission step**;
two requests admitted in the same step (between the same pair of decode
iterations) tie-break on the **highest request id** — a property of the
request, not of queue insertion order, so the victim is deterministic
however the trace was assembled.

Sampling is per-request: ``Request.temperature`` / ``Request.top_k`` /
``Request.top_p`` ride through per-slot vectors into one jitted sampler
call per step (``serving/sampling.py``); the default (temperature 0) is
greedy argmax.  The loop is host-driven, one slot-wise decode over the
whole pool per iteration, one device->host sync per step for the sampled
tokens.  Everything is deterministic for a fixed trace.

With ``spec_k > 0`` (plus a ``verify_fn``) each decode tick becomes a
draft-then-verify tick (``_spec_step``): a drafter proposes k tokens per
slot from the slot's own history, one verify step scores all k+1
positions against pool KV, and the slot accepts the longest draft prefix
matching the sequential sampler's own ``(rid, step)`` draws — so
speculative streams are bit-identical to ``spec_k == 0`` and a tick can
emit up to k+1 tokens per slot for one jitted call.

``run()`` drains a whole trace, but every phase is also exposed as a
step-wise API (``reset`` / ``try_admit`` / ``admit_from_queue`` / ``step``
/ ``stats``) so a ``ReplicaRouter`` can drive N schedulers in lockstep,
routing between them at admission time and catching solo page starvation
(``step(evict_on_starvation=True)`` hands the evicted entry back for
re-routing instead of raising).

**Prefill** has two modes (``chunk_step_fn`` + ``prefill_chunk``):

* ``prefill_chunk == 0`` — *blocking*: the whole (bucketed) prompt runs
  as one chunk inline at admission, exactly the old cadence — but the
  chunk step scatters its KV straight into the slot's pages, so even
  this path no longer materializes a whole-prompt ``(1, s)`` cache for
  ``insert`` to re-scatter.
* ``prefill_chunk > 0`` — *chunked*: admission reserves the slot and the
  prompt's pages, queues a ``PrefillJob``, and ``step`` interleaves at
  most ``prefill_chunk`` prompt tokens between decode ticks — in-flight
  requests keep streaming while a prompt is ingested.

With no ``chunk_step_fn`` the legacy path (``prefill_fn`` + pool
``insert``) is used unchanged.

TTFT is additionally tracked on a **virtual step clock** — a
deterministic wall-time proxy where every jitted model invocation
(decode tick, or one prefill chunk) costs one unit, and a blocking
prefill costs its chunk-equivalent ``ceil(n / chunk)`` *serially* (it
runs on the driver thread and stalls the loop — fleet-wide under the
lockstep router, which is exactly the stall chunking removes).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax.numpy as jnp
import numpy as np

from repro.serving.pool import PoolExhausted
from repro.serving.prefill import PrefillManager
from repro.serving.sampling import K_CAP, effective_top_k
from repro.serving.spec import NGramDrafter
from repro.serving.telemetry import span


def percentile_steps(values, q: float) -> float:
    """np.percentile over virtual-step samples; NaN for an idle fleet
    (no completed requests) — JSON writers map it to null."""
    if not values:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


class VirtualClock:
    """Deterministic step-count clock for the TTFT proxy: one unit per
    jitted model invocation.  ``advance_serial`` marks driver-thread work
    that stalls everyone (a blocking prefill at dispatch); on the plain
    clock it is the same as ``advance`` — the router's per-replica round
    view distinguishes the two."""

    def __init__(self):
        self._t = 0

    @property
    def t(self) -> int:
        return self._t

    def advance(self, n: int = 1) -> None:
        self._t += int(n)

    advance_serial = advance


class RoundClock:
    """A replica's view of a shared fleet clock during one lockstep round.

    Parallel-phase work (``advance``: decode ticks, prefill chunks)
    accumulates a local offset — at the end of the round the router
    advances the shared clock by the *max* offset across replicas, since
    real replicas work concurrently.  Serial-phase work
    (``advance_serial``: blocking prefill during dispatch) goes straight
    to the shared clock — the driver thread runs those one after another,
    stalling every replica's round."""

    def __init__(self, shared: VirtualClock):
        self.shared = shared
        self.offset = 0

    @property
    def t(self) -> int:
        return self.shared.t + self.offset

    def advance(self, n: int = 1) -> None:
        self.offset += int(n)

    def advance_serial(self, n: int = 1) -> None:
        self.shared.advance(n)

    def take(self) -> int:
        off, self.offset = self.offset, 0
        return off


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (s,) int32 token ids
    max_new_tokens: int = 16
    temperature: float = 0.0      # 0 = greedy
    top_k: int = 0                # 0 = no top-k filter
    top_p: float = 1.0            # 1 = no nucleus filter
    arrival_vstep: int = 0        # open-loop arrival on the virtual step
    #                               clock; 0 = available at t=0 (closed loop)


@dataclasses.dataclass
class RequestResult:
    rid: int
    prompt_len: int
    max_new_tokens: int
    slot: int = -1
    tokens: list = dataclasses.field(default_factory=list)
    preemptions: int = 0
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # virtual-step stamps (deterministic TTFT proxy; -1 = never reached)
    v_submit: int = 0
    v_first: int = -1
    v_done: int = -1

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def ttft_s(self) -> float:
        return self.t_first - self.t_submit

    @property
    def ttft_steps(self) -> int:
        """Time-to-first-token on the virtual step clock — deterministic
        for a fixed trace/fleet/policy, unlike wall-clock ttft_s."""
        return self.v_first - self.v_submit

    @property
    def e2e_steps(self) -> int:
        """Arrival-to-last-token latency on the virtual step clock."""
        return self.v_done - self.v_submit

    def meets_slo(self, slo_ttft_steps: int = 0,
                  slo_e2e_steps: int = 0) -> bool:
        """Did this request meet its deadlines?  Judged ONLY on virtual
        steps (never wall-clock); an unset deadline (<= 0) always passes."""
        if self.v_first < 0 or self.v_done < 0:
            return False
        if slo_ttft_steps > 0 and self.ttft_steps > slo_ttft_steps:
            return False
        if slo_e2e_steps > 0 and self.e2e_steps > slo_e2e_steps:
            return False
        return True


@dataclasses.dataclass
class ServeStats:
    results: list
    wall_s: float
    decode_steps: int
    generated_tokens: int
    occupancy: float              # mean active-slot fraction per decode step
    peak_active: int = 0          # max concurrent in-flight requests
    peak_resident_tokens: int = 0  # max KV tokens held across the pool
    preemptions: int = 0          # page-pressure evictions (paged pools)
    # chunked-prefill observability (zeros on the legacy prefill path)
    prefill_chunks: int = 0       # chunk-step invocations
    prefill_tokens: int = 0       # prompt tokens ingested through chunks
    prefill_compiles: int = 0     # distinct chunk buckets jitted
    prefill_queue_peak: int = 0   # max requests mid-prefill at once
    overlap_steps: int = 0        # steps that both chunked AND decoded
    mean_ttft_steps: float = 0.0  # mean virtual-clock time to first token
    # latency distribution + goodput, all on the virtual step clock (the
    # deterministic proxy) — never derived from wall_s.  Percentiles are
    # NaN when nothing completed (idle fleet); goodput counts the tokens
    # of requests that met the TTFT/e2e deadlines (deadline 0 = unset,
    # every completed request passes it)
    p50_ttft_steps: float = float("nan")
    p99_ttft_steps: float = float("nan")
    p50_e2e_steps: float = float("nan")
    p99_e2e_steps: float = float("nan")
    goodput_tokens: int = 0
    slo_ttft_steps: int = 0       # the deadlines goodput was judged by
    slo_e2e_steps: int = 0
    # shared-prefix KV cache observability (zeros with the cache off)
    prefix_hits: int = 0          # admissions that reused a cached run
    prefix_misses: int = 0        # admissions with no cached prefix
    prefill_tokens_saved: int = 0  # prompt tokens skipped via cache hits
    prefix_evictions: int = 0     # cache cells reclaimed under pressure
    # speculative decoding observability (zeros with spec_k == 0).
    # spec_verify_steps counts per-SLOT scoring events (one per active
    # slot per verify invocation), so accepted_per_verify is the clean
    # per-request speedup factor, not inflated by batch width
    spec_verify_steps: int = 0    # slot-verify scoring events
    spec_drafted_tokens: int = 0  # draft tokens proposed (k per slot-step)
    spec_accepted_tokens: int = 0  # draft tokens accepted (burst - 1 each)
    total_vsteps: int = 0         # virtual-clock span of the whole drain
    # effective per-request top-k after the vocab/K_CAP cap: {rid: k} for
    # every admitted request that asked for a top-k filter — surfaces what
    # the sampler actually applied instead of silently clamping
    effective_top_k: dict = dataclasses.field(default_factory=dict)
    # MoE routing, read from the pool's on-device counters once per
    # stats() call (never per tick): tokens routed to each held expert
    # {expert id: tokens} and the share of all top-k picks they took
    expert_tokens: dict = dataclasses.field(default_factory=dict)
    held_pick_share: float = float("nan")

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / max(self.wall_s, 1e-9)

    def to_metrics(self) -> dict:
        """Flat ``{key: number}`` snapshot of the single-engine drain —
        the scrape a dashboard would ingest.  Keys and kinds come from
        ``telemetry.SERVE_SCHEMA`` (the registry raises on a missing or
        undeclared key, so this view cannot silently drift from the
        schema); ``RouterStats.to_metrics`` is the same pattern over
        ``ROUTER_SCHEMA`` with a shared key suffix vocabulary."""
        from repro.serving.telemetry import SERVE_SCHEMA, MetricsRegistry
        reg = MetricsRegistry(SERVE_SCHEMA)
        reg.set("serve_requests_completed", len(self.results))
        reg.set("serve_generated_tokens", self.generated_tokens)
        reg.set("serve_goodput_tokens", self.goodput_tokens)
        reg.set("serve_slo_ttft_steps", self.slo_ttft_steps)
        reg.set("serve_slo_e2e_steps", self.slo_e2e_steps)
        reg.set("serve_ttft_p50_steps", self.p50_ttft_steps)
        reg.set("serve_ttft_p99_steps", self.p99_ttft_steps)
        reg.set("serve_e2e_p50_steps", self.p50_e2e_steps)
        reg.set("serve_e2e_p99_steps", self.p99_e2e_steps)
        reg.set("serve_mean_ttft_steps", self.mean_ttft_steps)
        reg.set("serve_total_vsteps", self.total_vsteps)
        reg.set("serve_wall_s", self.wall_s)
        reg.set("serve_tokens_per_s", self.tokens_per_s)
        reg.set("serve_decode_steps", self.decode_steps)
        reg.set("serve_occupancy", self.occupancy)
        reg.set("serve_peak_active", self.peak_active)
        reg.set("serve_peak_resident_kv", self.peak_resident_tokens)
        reg.set("serve_preemptions", self.preemptions)
        reg.set("serve_prefill_chunks", self.prefill_chunks)
        reg.set("serve_prefill_tokens", self.prefill_tokens)
        reg.set("serve_prefix_hits", self.prefix_hits)
        reg.set("serve_prefix_misses", self.prefix_misses)
        reg.set("serve_prefill_tokens_saved", self.prefill_tokens_saved)
        reg.set("serve_prefix_evictions", self.prefix_evictions)
        reg.set("serve_spec_verify_steps", self.spec_verify_steps)
        reg.set("serve_spec_drafted_tokens", self.spec_drafted_tokens)
        reg.set("serve_spec_accepted_tokens", self.spec_accepted_tokens)
        reg.set("serve_held_pick_share", self.held_pick_share)
        for e, n in self.expert_tokens.items():
            reg.set(f"serve_expert{e}_tokens", n)
        return reg.snapshot()

    @property
    def accepted_per_verify(self) -> float:
        """Tokens emitted per verify step (accepted drafts + the bonus
        token each step always emits) — > 1 means speculation is paying."""
        if not self.spec_verify_steps:
            return 0.0
        return (self.spec_verify_steps + self.spec_accepted_tokens) \
            / self.spec_verify_steps

    def summary(self) -> str:
        lat = [r.latency_s for r in self.results]
        pre = f", {self.preemptions} preemptions" if self.preemptions else ""
        if self.prefix_hits:
            pre += (f", {self.prefix_hits} prefix hits "
                    f"({self.prefill_tokens_saved}t prefill saved)")
        if self.spec_verify_steps:
            pre += (f", spec {self.accepted_per_verify:.2f} tok/verify "
                    f"({self.spec_accepted_tokens}/{self.spec_drafted_tokens}"
                    f" drafts accepted)")
        return (f"{len(self.results)} requests, {self.generated_tokens} tokens "
                f"in {self.wall_s:.3f}s -> {self.tokens_per_s:.1f} tok/s | "
                f"{self.decode_steps} decode steps, "
                f"occupancy {self.occupancy:.0%}, "
                f"peak {self.peak_active} in flight{pre} | latency "
                f"mean {np.mean(lat):.3f}s p max {np.max(lat):.3f}s")


@dataclasses.dataclass
class _Entry:
    """A queued unit of work: a fresh request, or a preempted one carrying
    the result it must resume (tokens generated so far).  ``rerouted``
    marks a solo-starvation eviction a router handed back: its pool
    provably cannot finish the request, so re-dispatch must place it by
    the pessimistic residency bound even under an optimistic eos."""
    req: Request
    st: RequestResult | None = None
    rerouted: bool = False
    probe_hit: object = None      # prefix-cache probe from the can_admit
    #                               immediately preceding _admit — attach
    #                               reuses it instead of re-walking keys

    @property
    def pending_len(self) -> int:
        """Prompt length at (re-)admission: original prompt plus anything
        already generated before a preemption."""
        n = len(self.req.prompt)
        return n + len(self.st.tokens) if self.st is not None else n

    def pending_tokens(self) -> np.ndarray:
        """The token prefix a (re-)admission must ingest — the prompt,
        plus everything generated before a preemption (a resume
        re-prefills both; the prefix cache keys on exactly these)."""
        prompt = np.asarray(self.req.prompt, np.int32)
        if self.st is not None and self.st.tokens:
            return np.concatenate(
                [prompt, np.asarray(self.st.tokens, np.int32)])
        return prompt

    def remaining_new(self) -> int:
        """Generation budget left (fresh entries: the full request ask)."""
        if self.st is None:
            return self.req.max_new_tokens
        return self.st.max_new_tokens - len(self.st.tokens)


@dataclasses.dataclass
class _Active:
    req: Request
    st: RequestResult
    admit_step: int               # decode step at admission; youngest is
    #                               the preemption victim, ties by req.rid


class Scheduler:
    """Drains a request queue through repeated slot-wise decode calls."""

    def __init__(self, pool, prefill_fn, decode_fn,
                 eos_id: int | None = None, policy: str = "continuous",
                 # advisory wall_s only; gated metrics are vstep-clocked
                 sampler=None, clock=time.perf_counter,  # easeylint: allow[wall-clock]
                 chunk_step_fn=None, prefill_chunk: int = 0,
                 prefill_chunk_unit: int = 16, vclock=None,
                 verify_fn=None, spec_k: int = 0, drafter=None,
                 vocab_size: int | None = None,
                 slo_ttft_steps: int = 0, slo_e2e_steps: int = 0,
                 tracer=None, replica_id: int = 0):
        if policy not in ("continuous", "static"):
            raise ValueError(policy)
        if prefill_chunk < 0 or prefill_chunk_unit < 1:
            raise ValueError((prefill_chunk, prefill_chunk_unit))
        if spec_k < 0:
            raise ValueError(f"spec_k {spec_k} < 0")
        if spec_k and verify_fn is None:
            raise ValueError("spec_k > 0 needs a verify_fn "
                             "(training/steps.build_verify_step_slots*)")
        self.pool = pool
        self.prefill_fn = prefill_fn        # (tokens (1,s)) -> logits, cache
        self.decode_fn = decode_fn          # (cache, tokens, active, *extras)
        self.chunk_step_fn = chunk_step_fn  # (cache, toks, slot, off, n, *x)
        self.prefill_chunk = prefill_chunk  # 0 = blocking full-prompt
        # chunk_unit prices a blocking prefill on the virtual clock (its
        # ceil(n/unit) chunk-equivalents) so blocking-vs-chunked TTFT is
        # compared in the same work units
        self.chunk_unit = prefill_chunk_unit
        self.eos_id = eos_id
        self.policy = policy
        self.sampler = sampler              # None -> greedy argmax
        self.clock = clock
        self.vclock = vclock or VirtualClock()
        # draft-then-verify speculative decoding: k drafts per slot, one
        # verify step scoring all k+1 positions (verify_fn), acceptance on
        # the host against the same (rid, step) sampler draws
        self.verify_fn = verify_fn          # (cache, toks, active, *extras)
        self.spec_k = spec_k
        self.drafter = drafter if drafter is not None else \
            (NGramDrafter() if spec_k else None)
        self.vocab_size = vocab_size        # for effective-top-k reporting
        # deadlines (virtual steps) goodput is judged by; 0 = unset
        self.slo_ttft_steps = int(slo_ttft_steps)
        self.slo_e2e_steps = int(slo_e2e_steps)
        # telemetry hook: every call site is guarded by `is not None`, so
        # tracing off costs one attribute load per event site and traces
        # never reach jitted code — spans/events are pure host bookkeeping
        # on the virtual clock and cannot move token streams
        self.tracer = tracer
        self.replica_id = int(replica_id)
        self.all_greedy = False
        self.reset()

    # -- step-wise state ----------------------------------------------------
    def reset(self, t0: float | None = None) -> None:
        """Fresh drain state (queue, active set, counters, host mirrors)."""
        S = self.pool.num_slots
        self.queue: deque = deque()
        self.active: dict[int, _Active] = {}
        self.done: list[RequestResult] = []
        self._last_tokens = np.zeros((S, 1), np.int32)
        self._active_mask = np.zeros((S,), np.int32)
        self._steps = 0
        self._busy = 0
        self._peak = 0
        self._peak_resident = 0
        self._preemptions = 0
        self._overlap = 0
        self._spec_verifies = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._eff_topk: dict[int, int] = {}
        self._t0 = self.clock() if t0 is None else t0
        self._v0 = self.vclock.t           # virtual submission time
        self._mgr = None if self.chunk_step_fn is None else \
            PrefillManager(self.pool, self.chunk_step_fn, self.prefill_chunk,
                           tracer=self.tracer, vclock=self.vclock,
                           replica_id=self.replica_id)
        pc = self.pool.prefix_cache
        if pc is not None and hasattr(pc, "bind_tracer"):
            pc.bind_tracer(self.tracer, self.vclock, self.replica_id)

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active or self.prefill_backlog)

    @property
    def prefill_backlog(self) -> bool:
        """Whether requests are mid-prefill (chunks still queued)."""
        return self._mgr is not None and self._mgr.has_jobs

    @property
    def in_flight(self) -> int:
        """Requests holding pool resources: actively decoding ones plus
        those mid-prefill (slot and pages reserved, chunks queued)."""
        jobs = len(self._mgr.jobs) if self._mgr is not None else 0
        return len(self.active) + jobs

    @property
    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens queued for ingestion but not yet chunked through
        — the backlog the router's TTFT napkin charges new arrivals."""
        return self._mgr.pending_tokens if self._mgr is not None else 0

    @property
    def free_tokens(self) -> int:
        """Router load signal: the pool's admittable tokens minus the
        prefill backlog still owed to it.  A replica mid-ingest has the
        HBM reserved but the compute pending — counting its queued
        chunks as free capacity would route new prompts straight into
        the stall chunking exists to hide."""
        backlog = self._mgr.pending_tokens if self._mgr is not None else 0
        return max(self.pool.free_tokens - backlog, 0)

    def validate(self, requests) -> None:
        """Reject up front what this pool could never serve: a mid-run
        rejection would throw away the stats of every request already
        served in a drain.  Without an eos, generation is deterministic
        full-length, so a paged request whose worst-case residency
        outstrips the whole page pool is *guaranteed* to starve.  (With
        an eos the request might stop early; it is admitted optimistically
        and the mid-decode starvation path still raises.)"""
        for req in requests:
            if len(req.prompt) > self.pool.max_len:
                raise ValueError(
                    f"request {req.rid}: prompt ({len(req.prompt)}) does "
                    f"not fit pool max_len {self.pool.max_len}")
            if not 0 <= req.top_k <= K_CAP:
                raise ValueError(
                    f"request {req.rid}: top_k {req.top_k} not in "
                    f"[0, {K_CAP}] — the sampler would silently clamp it")
            top_p = getattr(req, "top_p", 1.0)
            if not 0.0 < top_p <= 1.0:
                raise ValueError(
                    f"request {req.rid}: top_p {top_p} not in (0, 1]")
            if getattr(req, "arrival_vstep", 0) < 0:
                raise ValueError(
                    f"request {req.rid}: arrival_vstep "
                    f"{req.arrival_vstep} < 0")
            worst = self.worst_resident(_Entry(req))
            if not self.pool.can_ever_serve(worst):
                raise PoolExhausted(
                    f"request {req.rid} needs {worst} resident KV tokens "
                    f"but the pool can never hold that many")

    def worst_resident(self, entry: _Entry) -> int:
        """Max KV tokens `entry` will hold if admitted here (eos: only the
        pending prefill is certain; otherwise full-length generation is)."""
        if self.eos_id is not None:
            return entry.pending_len
        return min(entry.pending_len + entry.remaining_new() - 1,
                   self.pool.max_len)

    # -- sampling ----------------------------------------------------------
    def _sample_rows(self, logits_last, entries):
        """One sampler call over rows; entries[i] styles row i (None rows
        sample greedily with a dead key).  The ``serve.pick`` span covers
        the device-to-host wait for the picks."""
        one = entries[0] if len(entries) == 1 else None
        with span("pick", **({"rid": one.req.rid} if one else {})):
            if self.sampler is None or self.all_greedy:
                return np.asarray(jnp.argmax(logits_last, axis=-1))
            n = logits_last.shape[0]
            temps = np.zeros((n,), np.float32)
            topks = np.zeros((n,), np.int32)
            topps = np.ones((n,), np.float32)
            rids = np.zeros((n,), np.int32)
            steps = np.zeros((n,), np.int32)
            for i, en in enumerate(entries):
                if en is None:
                    continue
                temps[i] = en.req.temperature
                topks[i] = en.req.top_k
                topps[i] = getattr(en.req, "top_p", 1.0)
                rids[i] = en.req.rid
                steps[i] = len(en.st.tokens)
            return np.asarray(self.sampler(
                logits_last, jnp.asarray(temps), jnp.asarray(topks),
                jnp.asarray(topps), jnp.asarray(rids), jnp.asarray(steps)))

    def _sample_rows_multi(self, logits, width):
        """Sample ALL `width` speculated positions of every slot in one
        sampler call: row (slot, j) draws with the slot's request styling
        at generation step ``len(st.tokens) + j`` — the very key the
        sequential sampler would use if the j-th draft is accepted, which
        is what makes accepted bursts bit-identical to one-at-a-time
        decoding.  logits: (S, width, vocab) -> (S, width) int32."""
        if self.sampler is None or self.all_greedy:
            return np.asarray(jnp.argmax(logits, axis=-1))
        S = logits.shape[0]
        temps = np.zeros((S, width), np.float32)
        topks = np.zeros((S, width), np.int32)
        topps = np.ones((S, width), np.float32)
        rids = np.zeros((S, width), np.int32)
        steps = np.zeros((S, width), np.int32)
        for slot, en in self.active.items():
            temps[slot, :] = en.req.temperature
            topks[slot, :] = en.req.top_k
            topps[slot, :] = getattr(en.req, "top_p", 1.0)
            rids[slot, :] = en.req.rid
            steps[slot, :] = len(en.st.tokens) + np.arange(width)
        flat = self.sampler(
            logits.reshape(S * width, -1),
            jnp.asarray(temps.reshape(-1)), jnp.asarray(topks.reshape(-1)),
            jnp.asarray(topps.reshape(-1)), jnp.asarray(rids.reshape(-1)),
            jnp.asarray(steps.reshape(-1)))
        return np.asarray(flat).reshape(S, width)

    # -- admission ---------------------------------------------------------
    def _probe_prefix(self, entry: _Entry):
        """Read-only shared-prefix cache probe for `entry` (None when no
        cache is attached or prefill bypasses the chunk pipeline)."""
        cache = self.pool.prefix_cache
        if cache is None or self._mgr is None:
            return None
        return cache.probe(entry.pending_tokens())

    def can_admit(self, entry: _Entry) -> bool:
        """Admission asks the pool for the entry's *cold* footprint: with
        a prefix-cache hit only the un-cached suffix needs fresh pages.
        The probe rides on the entry so the ``_admit`` that immediately
        follows a True answer attaches it without re-walking the keys
        (a router's losing replicas overwrite it; the winner re-probes
        in ``try_admit`` right before admitting, so it is never stale)."""
        entry.probe_hit = self._probe_prefix(entry)
        return self.pool.can_admit(entry.pending_len, tuple(self.active),
                                   hit=entry.probe_hit)

    def try_admit(self, entry: _Entry) -> bool:
        """Router-facing single-entry admission; False when full."""
        if not self.can_admit(entry):
            return False
        self._admit(entry)
        return True

    def admit_from_queue(self) -> None:
        """Admit from the local queue head while the pool has room."""
        with span("admit") as sp:
            admitted = 0
            while self.queue and self.can_admit(self.queue[0]):
                self._admit(self.queue.popleft())
                admitted += 1
            sp.set_metadata(admitted=admitted)

    def _admit(self, entry: _Entry) -> None:
        now = self.clock()
        req = entry.req
        if self.tracer is not None:
            # close whichever wait span this request was in — "queued"
            # (fresh, begun at release) or "resume" (begun at preemption;
            # matching is on (rid, phase) so a reroute's resume closes
            # even when re-admission lands on another replica)
            self.tracer.end_any(("resume", "queued"), req.rid, self.vclock.t,
                                pending_tokens=int(entry.pending_len))
        if req.top_k:
            # surface what the sampler will actually apply (vocab and
            # K_CAP caps) — validated <= K_CAP, but a small-vocab model
            # can still cap below the ask
            self._eff_topk[req.rid] = effective_top_k(
                req.top_k, self.vocab_size or req.top_k)
        if entry.st is None:
            s = len(req.prompt)
            budget = self.pool.max_len - s + 1   # writes stop at max_len - 1
            st = RequestResult(
                rid=req.rid, prompt_len=s,
                max_new_tokens=min(req.max_new_tokens, budget),
                t_submit=getattr(req, "_t_submit", now),
                # open loop: latency is measured from the request's
                # *arrival* on the virtual clock, so queue wait counts
                v_submit=self._v0 + getattr(req, "arrival_vstep", 0))
            st.t_admit = now
            prompt = entry.pending_tokens()
        else:                                    # resume after preemption
            st = entry.st
            prompt = entry.pending_tokens()
        if self._mgr is not None:
            # pool-direct prefill: the slot and the prompt's pages are
            # reserved NOW (the same decision point blocking admission
            # reserved at, so admission order and token streams match);
            # a prefix-cache hit inside submit leaves only the cold
            # suffix for the chunk pipeline
            job = self._mgr.submit(entry, st, prompt)
            job.admit_step = self._steps
            if self.prefill_chunk:
                return                           # chunks interleave in step()
            # blocking: the un-cached remainder as one chunk, inline —
            # priced on the virtual clock at its chunk-equivalent cost,
            # *serially* (it runs on the driver thread and stalls the
            # lockstep loop)
            self.vclock.advance_serial(-(-job.remaining // self.chunk_unit))
            self._finish_prefill(job, self._mgr.drain(job))
            return
        # legacy path (no chunk step): prefill to a whole-prompt (1, s)
        # cache, then scatter it into the pool.  Prefill lengths are
        # bucketed to powers of two so resumes (whose lengths are
        # arbitrary) reuse one compiled prefill per bucket: the prompt is
        # right-padded, logits are read at the true last position, and
        # the cache is sliced back before insertion (causal attention
        # keeps positions < n independent of the padding)
        n = len(prompt)
        pad = 1 << (n - 1).bit_length()
        if pad == n:
            logits, cache = self.prefill_fn(jnp.asarray(prompt)[None, :])
        else:
            padded = np.zeros((pad,), np.int32)
            padded[:n] = prompt
            logits, cache = self.prefill_fn(jnp.asarray(padded)[None, :],
                                            n - 1)
            cache = {"k": cache["k"][:, :, :n], "v": cache["v"][:, :, :n],
                     "index": jnp.asarray(n, jnp.int32)}
        self.vclock.advance_serial(-(-n // self.chunk_unit))
        tok = int(self._sample_rows(logits[:, -1], [_Active(req, st, 0)])[0])
        if entry.st is None:
            st.t_first = self.clock()
            st.v_first = self.vclock.t
        st.tokens.append(tok)
        if len(st.tokens) >= st.max_new_tokens or tok == self.eos_id:
            st.t_done = self.clock()
            st.v_done = self.vclock.t
            self.done.append(st)
            return
        slot = self.pool.alloc()
        st.slot = slot
        self.pool.insert(slot, cache)
        self.active[slot] = _Active(req, st, self._steps)
        self._last_tokens[slot, 0] = tok
        self._active_mask[slot] = 1
        if self.tracer is not None:
            self.tracer.begin("decode", req.rid, self.vclock.t,
                              replica=self.replica_id, slot=slot,
                              resident_tokens=int(self.pool.lengths[slot]))

    def _finish_prefill(self, job, logits) -> None:
        """A job's final chunk landed: sample the first token and either
        finish the request or activate its (already-populated) slot."""
        st, req = job.st, job.entry.req
        tok = int(self._sample_rows(logits[:, -1], [_Active(req, st, 0)])[0])
        if job.entry.st is None:
            st.t_first = self.clock()
            st.v_first = self.vclock.t
        st.tokens.append(tok)
        if len(st.tokens) >= st.max_new_tokens or tok == self.eos_id:
            st.t_done = self.clock()
            st.v_done = self.vclock.t
            self.done.append(st)
            self.pool.free(job.slot)
            return
        st.slot = job.slot
        self.active[job.slot] = _Active(req, st, job.admit_step)
        self._last_tokens[job.slot, 0] = tok
        self._active_mask[job.slot] = 1
        if self.tracer is not None:
            self.tracer.begin("decode", req.rid, self.vclock.t,
                              replica=self.replica_id, slot=job.slot,
                              resident_tokens=int(
                                  self.pool.lengths[job.slot]))

    # -- preemption --------------------------------------------------------
    def _evict(self, slot: int) -> _Entry:
        """Free `slot` and return its request as a resumable entry."""
        en = self.active.pop(slot)
        en.st.slot = -1
        en.st.preemptions += 1
        if self.tracer is not None:
            v = self.vclock.t
            self.tracer.end("decode", en.st.rid, v, preempted=True,
                            tokens=len(en.st.tokens))
            self.tracer.instant("preempt", v, replica=self.replica_id,
                                rid=en.st.rid, slot=slot,
                                tokens=len(en.st.tokens))
            self.tracer.begin("resume", en.st.rid, v,
                              replica=self.replica_id)
        self._active_mask[slot] = 0
        self._last_tokens[slot, 0] = 0
        self.pool.free(slot)                 # returns its pages
        return _Entry(en.req, en.st)

    def _preempt(self, slot: int) -> None:
        self.queue.appendleft(self._evict(slot))
        self._preemptions += 1

    # -- one decode iteration ----------------------------------------------
    def _requeue_job(self, job) -> None:
        """Re-queue an evicted mid-prefill job at the queue front.  A
        fresh job (no tokens yet) restarts from scratch; a resume job
        keeps its result so the already-emitted tokens survive."""
        st = job.st if job.st.tokens else None
        if st is not None:
            st.slot = -1
            st.preemptions += 1
        if self.tracer is not None:
            rid = job.entry.req.rid
            v = self.vclock.t
            self.tracer.instant("preempt", v, replica=self.replica_id,
                                rid=rid, mid_prefill=True,
                                ingested=int(job.done))
            self.tracer.begin("resume", rid, v, replica=self.replica_id)
        self.queue.appendleft(_Entry(job.entry.req, st))
        self._preemptions += 1

    def step(self, evict_on_starvation: bool = False) -> list:
        """One scheduler tick: ingest at most ``prefill_chunk`` queued
        prompt tokens, then one slot-wise decode over the active set.

        Paged pools grow slots crossing a page boundary first; starvation
        preempts mid-prefill jobs first (youngest — they have ingested
        the least), then the youngest in-flight request (ties by request
        id) until the step fits.  When the *sole* active request starves
        the pool can never make progress alone: raise ``PoolExhausted``,
        or — under a router (``evict_on_starvation``) — hand the evicted
        entry back for re-routing to a replica that can hold it.  Returns
        the evicted entries (empty in the single-engine path).

        The tick runs under the ``serve.step`` profiler span (``vstep``:
        the virtual clock at its start), its phases under ``serve.prefill``,
        ``serve.page``, ``serve.decode`` (or ``serve.verify``),
        ``serve.pick`` and ``serve.finish`` (``telemetry.span``).
        """
        with span("step", vstep=self.vclock.t, active=len(self.active)):
            return self._step(evict_on_starvation)

    def _step(self, evict_on_starvation: bool) -> list:
        chunked = 0
        if self._mgr is not None and self._mgr.has_jobs:
            with span("prefill"):
                self._peak = max(self._peak, self.in_flight)
                finished, chunked = self._mgr.tick(self.vclock)
                for job, logits in finished:
                    self._finish_prefill(job, logits)
        if not self.active:
            return []
        evicted = []
        with span("page"):
            while True:
                starved = self.pool.prepare_decode(sorted(self.active))
                if not starved:
                    break
                if self._mgr is not None and self._mgr.has_jobs:
                    self._requeue_job(self._mgr.evict_newest())
                    continue
                if len(self.active) == 1:
                    (slot,) = self.active
                    if not evict_on_starvation:
                        raise PoolExhausted(
                            f"page starvation mid-decode: request "
                            f"{self.active[slot].req.rid} holds every page "
                            f"and still needs another — the page pool is "
                            f"too small for it")
                    evicted.append(self._evict(slot))
                    self._preemptions += 1
                    return evicted           # nothing left to decode
                victim = max(self.active,
                             key=lambda sl: (self.active[sl].admit_step,
                                             self.active[sl].req.rid))
                self._preempt(victim)
        self._peak = max(self._peak, self.in_flight)
        self._peak_resident = max(self._peak_resident,
                                  int(self.pool.lengths.sum()))
        if self.spec_k and self.verify_fn is not None:
            with span("verify"):
                self._spec_step(chunked)
            return evicted
        with span("decode"):
            logits, new_cache = self.decode_fn(
                self.pool.cache, jnp.asarray(self._last_tokens),
                jnp.asarray(self._active_mask), *self.pool.decode_extras())
            self.pool.update(new_cache, tuple(self.active))
        self.vclock.advance(1)
        self._steps += 1
        self._busy += len(self.active)
        if chunked:
            self._overlap += 1       # ingested a chunk AND decoded a token
        S = self.pool.num_slots
        rows = [self.active.get(i) for i in range(S)]
        toks = self._sample_rows(logits[:, -1], rows)
        with span("finish"):
            now = self.clock()
            vnow = self.vclock.t
            for slot, en in list(self.active.items()):
                st = en.st
                tok = int(toks[slot])
                st.tokens.append(tok)
                self._last_tokens[slot, 0] = tok
                if len(st.tokens) >= st.max_new_tokens or \
                        tok == self.eos_id:
                    st.t_done = now
                    st.v_done = vnow
                    self.done.append(st)
                    del self.active[slot]
                    self._active_mask[slot] = 0
                    self._last_tokens[slot, 0] = 0
                    self.pool.free(slot)
                    if self.tracer is not None:
                        self.tracer.end("decode", st.rid, vnow,
                                        tokens=len(st.tokens))
        return evicted

    # -- speculative decode -------------------------------------------------
    def _spec_step(self, chunked: int) -> None:
        """One draft-then-verify tick over the active set.

        Per slot: the drafter proposes k tokens from the slot's own
        history; the verify step scores all k+1 positions (pending token
        + drafts) against pool KV in one jitted call; every position is
        sampled with the sequential sampler's own ``(rid, step)`` key;
        the slot then accepts the longest prefix of draws that matches
        its drafts — exactly the tokens one-at-a-time decode would have
        produced, so speculative streams are bit-identical to spec_k=0.

        Page charging: ``prepare_decode`` already granted the mandatory
        next-token position (same starvation/preemption semantics as
        non-speculative decode); ``grow_for_burst`` then backs as much of
        the burst as genuinely free pages allow, acceptance is capped at
        the backed count, and any verify write past it lands in junk
        page 0 via the attention ok-guard — never in a live (possibly
        prefix-shared) page.  KV written for rejected drafts is
        overwritten by the next step before any causal mask admits it.
        The device index is not advanced by the verify step (acceptance
        is a host decision): ``pool.sync_index`` re-uploads the length
        mirror once per tick.
        """
        S = self.pool.num_slots
        k = self.spec_k
        tok_mat = np.zeros((S, k + 1), np.int32)
        caps = np.zeros((S,), np.int64)
        drafts: dict[int, list] = {}
        for slot, en in self.active.items():
            hist = np.asarray(en.req.prompt).tolist() + en.st.tokens
            d = self.drafter.draft(hist, k)
            drafts[slot] = d
            tok_mat[slot, 0] = self._last_tokens[slot, 0]
            tok_mat[slot, 1:] = d
            caps[slot] = self.pool.grow_for_burst(slot, k + 1)
        logits, new_cache = self.verify_fn(
            self.pool.cache, jnp.asarray(tok_mat),
            jnp.asarray(self._active_mask), *self.pool.decode_extras())
        self.pool.adopt(new_cache)
        self.vclock.advance(1)
        self._steps += 1
        self._busy += len(self.active)
        if chunked:
            self._overlap += 1
        toks = self._sample_rows_multi(logits, k + 1)
        now = self.clock()
        vnow = self.vclock.t
        for slot, en in list(self.active.items()):
            st = en.st
            d = drafts[slot]
            cap = int(caps[slot])        # >= 1: prepare_decode granted it
            emitted = 0
            j = 0
            finished = False
            while True:
                tok = int(toks[slot, j])
                st.tokens.append(tok)
                emitted += 1
                if len(st.tokens) >= st.max_new_tokens or \
                        tok == self.eos_id:
                    finished = True
                    break
                # j == k: no draft beyond position k to validate;
                # emitted == cap: sample j+1's query position is not
                # backed by a page; tok != d[j]: the draft fed at
                # position j+1 is not what sequential decode would see
                if j >= k or emitted >= cap or tok != d[j]:
                    break
                j += 1
            self._spec_verifies += 1
            self._spec_drafted += k
            self._spec_accepted += emitted - 1
            if self.tracer is not None:
                self.tracer.span("spec_verify", st.rid, vnow - 1, vnow,
                                 replica=self.replica_id, slot=slot,
                                 k=k, emitted=emitted,
                                 accepted=emitted - 1, backed=cap)
            self.pool.set_length(slot,
                                 int(self.pool.lengths[slot]) + emitted)
            if finished:
                st.t_done = now
                st.v_done = vnow
                self.done.append(st)
                del self.active[slot]
                self._active_mask[slot] = 0
                self._last_tokens[slot, 0] = 0
                self.pool.free(slot)
                if self.tracer is not None:
                    self.tracer.end("decode", st.rid, vnow,
                                    tokens=len(st.tokens))
            else:
                self._last_tokens[slot, 0] = int(toks[slot, emitted - 1])
        self.pool.sync_index()

    # -- results -----------------------------------------------------------
    def stats(self) -> ServeStats:
        wall = self.clock() - self._t0
        done = sorted(self.done, key=lambda r: r.rid)
        ttfts = [r.ttft_steps for r in done if r.v_first >= 0]
        e2es = [r.e2e_steps for r in done if r.v_done >= 0]
        goodput = sum(
            len(r.tokens) for r in done
            if r.meets_slo(self.slo_ttft_steps, self.slo_e2e_steps))
        mgr = self._mgr
        pc = self.pool.prefix_cache
        expert_tokens, held_share = self._routing()
        return ServeStats(
            results=done, wall_s=wall, decode_steps=self._steps,
            generated_tokens=sum(len(r.tokens) for r in done),
            occupancy=self._busy / max(self._steps * self.pool.num_slots, 1),
            peak_active=self._peak, peak_resident_tokens=self._peak_resident,
            preemptions=self._preemptions,
            prefill_chunks=mgr.chunks_run if mgr else 0,
            prefill_tokens=mgr.tokens_ingested if mgr else 0,
            prefill_compiles=len(mgr.compiled_buckets) if mgr else 0,
            prefill_queue_peak=mgr.queue_peak if mgr else 0,
            overlap_steps=self._overlap,
            mean_ttft_steps=float(np.mean(ttfts)) if ttfts else 0.0,
            p50_ttft_steps=percentile_steps(ttfts, 50),
            p99_ttft_steps=percentile_steps(ttfts, 99),
            p50_e2e_steps=percentile_steps(e2es, 50),
            p99_e2e_steps=percentile_steps(e2es, 99),
            goodput_tokens=goodput,
            slo_ttft_steps=self.slo_ttft_steps,
            slo_e2e_steps=self.slo_e2e_steps,
            prefix_hits=pc.hits if pc else 0,
            prefix_misses=pc.misses if pc else 0,
            prefill_tokens_saved=pc.tokens_saved if pc else 0,
            prefix_evictions=pc.evictions if pc else 0,
            spec_verify_steps=self._spec_verifies,
            spec_drafted_tokens=self._spec_drafted,
            spec_accepted_tokens=self._spec_accepted,
            total_vsteps=self.vclock.t - self._v0,
            effective_top_k=dict(self._eff_topk),
            expert_tokens=expert_tokens, held_pick_share=held_share)

    def _routing(self) -> tuple:
        """({held expert: tokens routed to it}, share of all picks on
        held experts) from the pool's ``route_counts``, summed over
        layers and over every step the pool has run; ({}, NaN) without
        them."""
        counts = (self.pool.cache or {}).get("route_counts")
        if counts is None:
            return {}, float("nan")
        counts = np.asarray(counts)
        cfg = self.pool.cfg
        first = cfg.expert_offset
        held = range(first, first + (cfg.experts_held or cfg.num_experts))
        total = int(counts.sum())
        share = sum(int(counts[e]) for e in held) / total if total \
            else float("nan")
        return {e: int(counts[e]) for e in held}, share

    # -- main loop ---------------------------------------------------------
    def run(self, requests) -> ServeStats:
        """Drain a trace.  Closed-loop traces (every ``arrival_vstep``
        0) queue everything up front, exactly the old behaviour.  Open-
        loop traces release each request only once the virtual clock
        reaches its arrival; an idle pool with only future arrivals
        fast-forwards the clock to the next one (real time passes while
        nothing computes), so the schedule stays deterministic."""
        requests = list(requests)
        self.validate(requests)
        # all-greedy traces skip the sampler (argmax is its temperature-0 /
        # top_k-1 special case, so this is a pure fast path)
        self.all_greedy = all(r.temperature <= 0 or r.top_k == 1
                              for r in requests)
        self.reset()
        # stable sort: ties (and the all-zero closed loop) keep trace order
        pending = deque(sorted((_Entry(r) for r in requests),
                        key=lambda en: getattr(en.req, "arrival_vstep", 0)))
        for r in requests:
            r._t_submit = self._t0
        while pending or self.has_work:
            while pending and self._v0 + \
                    getattr(pending[0].req, "arrival_vstep", 0) \
                    <= self.vclock.t:
                en = pending.popleft()
                if self.tracer is not None:
                    # the wait span starts at *arrival*, not release: a
                    # fast-forwarded idle gap still counts as queue time 0
                    self.tracer.begin(
                        "queued", en.req.rid,
                        self._v0 + getattr(en.req, "arrival_vstep", 0),
                        replica=self.replica_id,
                        prompt_len=len(en.req.prompt))
                self.queue.append(en)
            if self.policy == "continuous" or \
                    not (self.active or self.prefill_backlog):
                self.admit_from_queue()
            if not self.active and not self.prefill_backlog:
                if self.queue:
                    en = self.queue[0]
                    raise PoolExhausted(
                        f"request {en.req.rid} ({en.pending_len} tokens) "
                        f"cannot be admitted into an otherwise idle pool — "
                        f"the KV pool is too small for it")
                if pending:
                    nxt = self._v0 + pending[0].req.arrival_vstep
                    self.vclock.advance(nxt - self.vclock.t)
                continue
            self.step()
        if self.tracer is not None:
            self.tracer.close(self.vclock.t)
        return self.stats()
