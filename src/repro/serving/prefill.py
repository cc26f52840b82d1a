"""PrefillManager — chunked prompt ingestion as its own schedulable stage.

Prompt ingestion used to be an inline side effect of admission: the
scheduler ran one blocking full-prompt prefill, materialized a contiguous
``(1, s)`` cache, and ``insert`` re-scattered it into the pool — stalling
the decode loop for the whole prompt and (under the router) stalling the
whole lockstep fleet, since admissions run serially on the driver thread.
This module splits prefill out, the way EASEY's middleware layer splits a
tunable stage out of a monolithic deployment step:

* a prompt is cut into fixed-size **chunks** (the tuner's
  ``plan.serve_prefill_chunk``); each chunk is padded to a power-of-two
  bucket so the jit cache stays at ~log2(chunk) entries;
* each chunk runs through the **chunk-prefill step**
  (``training/steps.build_prefill_chunk_step[_paged]``), which computes
  the chunk's KV and scatters it **directly into pool slots/pages** —
  its final resting place, one write, no contiguous intermediate — while
  attending causally over every prior chunk through the pool's own
  indirection (page table or slot row);
* the scheduler interleaves at most one chunk budget's worth of prefill
  tokens between decode ticks (``Scheduler.step``), so in-flight requests
  keep streaming while a new prompt is ingested, and a router overlaps
  replica A's ingestion with B/C's decode ticks.

The pool reservation (slot + all prompt pages) happens at **submit** —
the same decision point blocking admission reserved at — so admission
order, preemption behaviour, and therefore every token stream are
identical to the blocking path.  Blocking mode itself is just the
degenerate manager: one chunk covering the whole (bucketed) prompt,
drained inline at admission.

When the pool carries a shared-prefix cache
(``serving/prefix_cache.PrefixCache``), ``submit`` probes it first: a hit
installs the cached page run into the slot by pointer copy and starts the
ingest cursor *past* the shared prefix, so only the cold suffix is ever
cut into chunks — zero chunk steps and zero KV writes for the reused
part.  The final chunk of every prompt registers its fully-covered pages
back into the cache, so the first request over a prefix pays for all its
successors.

Counters (chunks run, tokens ingested, distinct compiled buckets, queue
peak, cache hits/misses/saved tokens) feed ``Scheduler.stats`` — the
observability the tuner's chunk-size and cache-budget choices are judged
against.
"""

from __future__ import annotations

import dataclasses
from collections import deque

import jax.numpy as jnp
import numpy as np

from repro.serving.telemetry import span


def bucket_len(n: int) -> int:
    """Power-of-two jit bucket for an `n`-token chunk."""
    return 1 << (max(n, 1) - 1).bit_length()


@dataclasses.dataclass
class PrefillJob:
    """One request's prompt mid-ingestion: the scheduler entry it will
    activate, the full pending token prefix (prompt plus anything already
    generated before a preemption), and the ingest cursor."""
    entry: object                  # scheduler _Entry
    st: object                     # RequestResult being (re)built
    prompt: np.ndarray             # (n,) int32 pending prefix
    slot: int
    done: int = 0                  # tokens already scattered into the pool
    chunks: int = 0                # chunk steps run so far (trace span index)
    admit_step: int = 0            # scheduler step at SUBMISSION — the
    #                                preemption-age stamp, so the victim
    #                                choice matches blocking admission
    #                                however ingestion was interleaved

    @property
    def remaining(self) -> int:
        return len(self.prompt) - self.done


class PrefillManager:
    """Chunk queue + chunk-step driver over one KV pool.

    ``chunk_tokens`` is the interleave grain: ``tick`` ingests at most
    that many prompt tokens per call (0 means whole-prompt chunks — the
    blocking degenerate, driven via ``drain``).
    """

    def __init__(self, pool, chunk_step, chunk_tokens: int = 0,
                 tracer=None, vclock=None, replica_id: int = 0):
        if chunk_tokens < 0:
            raise ValueError(f"chunk_tokens {chunk_tokens} < 0")
        self.pool = pool
        self.chunk_step = chunk_step   # (cache, toks, slot, off, n, *extras)
        self.chunk_tokens = chunk_tokens
        # telemetry hook (None = off): host-side span bookkeeping only,
        # recorded after each chunk lands — never inside the jitted step
        self.tracer = tracer
        self.vclock = vclock
        self.replica_id = int(replica_id)
        self.jobs: deque[PrefillJob] = deque()
        # observability: the tuner's chunk-size choice is judged on these
        self.chunks_run = 0
        self.tokens_ingested = 0
        self.compiled_buckets: set[int] = set()
        self.queue_peak = 0

    # -- state ---------------------------------------------------------------
    @property
    def has_jobs(self) -> bool:
        return bool(self.jobs)

    @property
    def pending_tokens(self) -> int:
        """Prompt tokens still owed to the pool — the ingest backlog a
        router's least-loaded policy charges against free capacity."""
        return sum(j.remaining for j in self.jobs)

    @property
    def prefix_cache(self):
        """The pool's attached shared-prefix cache (None when disabled)."""
        return getattr(self.pool, "prefix_cache", None)

    # -- lifecycle -----------------------------------------------------------
    def submit(self, entry, st, prompt: np.ndarray) -> PrefillJob:
        """Reserve the slot and the prompt's pages, queue the job.

        A prefix-cache hit adopts the shared page run first (pointer
        copies + a reference per page) and reserves pages only for the
        cold suffix; the job's cursor starts past the cached tokens, so
        its chunks cover the suffix alone."""
        prompt = np.asarray(prompt, np.int32)
        slot = self.pool.alloc()
        cached = 0
        if self.prefix_cache is not None:
            cached = self.prefix_cache.attach(
                slot, prompt, getattr(entry, "probe_hit", None))
        try:
            self.pool.reserve_prefix(slot, len(prompt))
        except Exception:
            self.pool.free(slot)   # also drops the shared run's references
            raise
        if cached:
            self.pool.set_length(slot, cached)
        job = PrefillJob(entry=entry, st=st, prompt=prompt, slot=slot,
                         done=cached)
        if self.tracer is not None and self.prefix_cache is not None:
            # zero-width span: the probe + pointer-copy adoption happens
            # at a single vstep, but hit/miss and tokens reused matter
            t = self.vclock.t if self.vclock is not None else 0
            self.tracer.span("cache_attach", st.rid, t, t,
                             replica=self.replica_id, slot=slot,
                             hit=bool(cached), tokens_cached=int(cached))
        self.jobs.append(job)
        self.queue_peak = max(self.queue_peak, len(self.jobs))
        return job

    def evict_newest(self):
        """Drop the youngest queued job (deterministic page-pressure
        relief: it has ingested the least), free its slot and pages, and
        return the job for the scheduler to re-queue."""
        job = self.jobs.pop()
        self.pool.free(job.slot)
        return job

    # -- chunk execution -----------------------------------------------------
    def _run_chunk(self, job: PrefillJob):
        """Ingest one chunk of `job`; returns the chunk's last-position
        logits when it was the final chunk, else None.  Host prep, the
        page-table upload and the dispatch run under ``serve.chunk``."""
        c = min(self.chunk_tokens or job.remaining, job.remaining)
        bucket = bucket_len(c)
        # static KV read-back bound: the chunk attends its own bucketed
        # prefix, not the pool's max_len (bound buckets x chunk buckets
        # is the whole chunk jit cache)
        bound = min(bucket_len(job.done + c), self.pool.kv_bound_cap)
        with span("chunk", rid=job.st.rid, slot=job.slot, tokens=c,
                  bucket=bucket, bound=bound):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :c] = job.prompt[job.done:job.done + c]
            extras = self.pool.chunk_extras(job.slot)
            logits, new_cache = self.chunk_step(
                self.pool.cache, jnp.asarray(toks), jnp.int32(job.slot),
                jnp.int32(job.done), jnp.int32(c), bound, *extras)
            self.pool.adopt(new_cache)
        if self.tracer is not None:
            # each chunk is one vclock unit; tick()/drain() advance the
            # clock right after this returns, so the span is (t, t+1)
            t = self.vclock.t if self.vclock is not None else 0
            self.tracer.span("prefill_chunk", job.st.rid, t, t + 1,
                             replica=self.replica_id, slot=job.slot,
                             index=job.chunks, tokens=c, bucket=bucket,
                             offset=job.done)
        job.chunks += 1
        job.done += c
        self.chunks_run += 1
        self.tokens_ingested += c
        # the jit cache key is the (chunk bucket, kv bound) PAIR — bound
        # is a static argument, so each pair is its own compile
        self.compiled_buckets.add((bucket, bound))
        # keep the host length mirror current per chunk: mid-ingest KV is
        # resident HBM and must show up in peak_resident_tokens (lengths
        # of non-active slots are never consulted for decode growth)
        self.pool.set_length(job.slot, job.done)
        if job.done == len(job.prompt):
            if self.prefix_cache is not None:
                # the run is fully written and read-only from here on:
                # register its prompt-covered pages for future sharers
                self.prefix_cache.insert(job.prompt, job.slot)
            return logits
        return None

    def tick(self, vclock=None):
        """Ingest up to ``chunk_tokens`` prompt tokens (head-of-line).

        Returns ``(finished, invocations)`` where finished is a list of
        ``(job, logits)`` for jobs whose final chunk just landed.  Each
        chunk is one jitted invocation and advances ``vclock`` by one —
        the deterministic unit the TTFT proxy is measured in.
        """
        budget = self.chunk_tokens or (self.jobs[0].remaining
                                       if self.jobs else 0)
        finished, invocations = [], 0
        while self.jobs and budget >= min(
                self.chunk_tokens or self.jobs[0].remaining,
                self.jobs[0].remaining):
            job = self.jobs[0]
            take = min(self.chunk_tokens or job.remaining, job.remaining)
            logits = self._run_chunk(job)
            invocations += 1
            budget -= take
            if vclock is not None:
                vclock.advance(1)
            if logits is not None:
                self.jobs.popleft()
                finished.append((job, logits))
        return finished, invocations

    def drain(self, job: PrefillJob):
        """Blocking path: run every remaining chunk of `job` now (it must
        be the queue tail just submitted); returns the final logits."""
        assert self.jobs and self.jobs[-1] is job
        self.jobs.pop()
        logits = None
        while logits is None:
            logits = self._run_chunk(job)
        return logits
