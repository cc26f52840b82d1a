"""The serving driver: one cell of traffic through ``ServeEngine``.

What it touches of the program (the program's contract with the
benchmark, listed in PERF.md):

* ``repro.configs.base``: ``ARCHS``, ``get_config``, ``register`` (a
  configuration the program lacks is registered from its file);
* ``repro.serving.engine``: ``ServeEngine`` (``prefill_fn``, ``decode_fn``,
  whose logits are ``(slots, 1, vocab)``, ``chunk_fn``, ``make_pool``,
  ``sampler``, ``prefill_chunk``, ``chunk_unit``, ``kv_kernel``, ``cfg``
  and its sizes) and its module-level ``init_params``, which is replaced
  while the engine is built so that it serves the benchmark's weights;
* ``repro.serving.scheduler``: ``Scheduler`` (``queue``, ``active``,
  ``done``, ``admit_from_queue``, ``step``, ``validate``, ``stats``,
  ``prefill_backlog``, ``in_flight``, ``all_greedy``), ``Request`` and
  ``_Entry``;
* ``repro.serving.prefill.bucket_len`` and the pool's ``lengths``,
  ``kv_bound_cap``, ``reserve_prefix``, ``chunk_extras``,
  ``decode_extras``, ``alloc``, ``free``, ``adopt``, ``update``,
  ``cache``, ``num_slots``;
* ``repro.launch.compile_cache`` (from ``bench/run.py``).

Open loop (``loop: open``): requests are released into the scheduler's
queue when they fall due on the wall clock; time to first token is
counted from the due time.  Closed loop (``loop: closed``): each of the
clients sends its next request as soon as its last one finishes; the
clients' first requests are admitted and prefilled during set-up.

Set-up makes the weights (``bench/weights.py``), builds the engine and
its pool, and runs every program the traffic reaches once: each (chunk
bucket, KV bound) pair of the chunk step that a prompt length in the
traffic's range crosses, the decode step and the greedy pick.  The
window then drives ``Scheduler.step``; a program compiled or loaded
inside it fails the run.

The check (``reference.readings``) compares a seeded sample of the
served requests with the float32 reference: the tokens, and the logit
each decode step picked them from, read on the device after each step.
The limits are the traffic file's ``check.limits``.
"""

from __future__ import annotations

import gc
import os
import shutil
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic as traffic_mod, weights as weights_mod
from bench.trace_reduce import load_events, reduce_events

# a traced run records this stretch of the window
TRACE_START_SHARE = 0.25
TRACE_MAX_S = 10.0
# the check compares at least this many served tokens, from requests
# drawn by the seed; the longest finished request is always among them
CHECK_SALT = 0x5EED
CLOSED_SETUP_TICK_LIMIT = 100000


def _annotate(name):
    return jax.profiler.TraceAnnotation(name)


@jax.jit
def top_logit(logits):
    """Per slot, the logit a decode step's greedy pick is made from."""
    return jnp.max(logits[:, -1], axis=-1).astype(jnp.float32)


def program_arch(config: dict) -> str:
    """The program's name for the configuration, registering it from the
    file's ``program`` section when the program lacks it; raises when the
    program's sizes differ from the published ones."""
    from repro.configs.base import ARCHS, get_config, register
    prog = config["program"]
    name = config["name"]
    if name not in ARCHS:
        base = get_config(prog["arch"])
        cfg = base.replace(name=name, **prog.get("overrides", {}))
        smoke = cfg.replace(name=name + "-unused-smoke")
        register(cfg, smoke)
    cfg = get_config(name)
    m = reference.model_dims(config["config"])
    got = {"d": cfg.d_model, "heads": cfg.num_heads,
           "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
           "layers": cfg.num_layers, "norm": cfg.norm,
           "qkv_bias": cfg.qkv_bias, "rope_fraction": cfg.rope_fraction,
           "rope_theta": cfg.rope_theta}
    want = {k: m[k] for k in got}
    if got != want or cfg.tie_embeddings or cfg.family != "dense":
        raise ValueError(f"program config {name} differs from the "
                         f"published one: {got} vs {want}")
    return name


def chunk_programs(prompt_min: int, prompt_max: int, chunk: int,
                   bound_cap: int) -> list:
    """Every (chunk bucket, KV bound) pair the prefill manager reaches for
    prompts of these lengths (the rule of ``PrefillManager._run_chunk``)."""
    from repro.serving.prefill import bucket_len
    pairs = set()
    for n in range(prompt_min, prompt_max + 1):
        done = 0
        while done < n:
            c = min(chunk or n, n - done)
            pairs.add((bucket_len(c), min(bucket_len(done + c), bound_cap)))
            done += c
    return sorted(pairs)


class Recorder:
    """Per-request token times, taken after each scheduler tick (the
    first token at the scheduler's own stamp), the logits each decode
    step picked from (on the device, with the (slot, request, token
    index) of each active slot), and the live KV lengths of each decode
    call while a trace is on."""

    def __init__(self):
        self.times: dict = {}
        self.seen: dict = {}
        self.done_seen = 0
        self.tracing = False
        self.decode_live: list = []
        self.tops: list = []

    def picked_from(self) -> dict:
        """(request id, token index) -> the logit its pick was made from."""
        values = jax.device_get([t for t, _ in self.tops])
        return {(rid, idx): float(v[slot])
                for v, (_, where) in zip(values, self.tops)
                for slot, rid, idx in where}

    def note(self, st, now: float) -> None:
        n = len(st.tokens)
        k = self.seen.get(st.rid, 0)
        if n <= k:
            return
        times = self.times.setdefault(st.rid, [])
        if k == 0:
            times.append(st.t_first)
            k = 1
        times.extend([now] * (n - k))
        self.seen[st.rid] = n

    def observe(self, sched, now: float) -> list:
        """Stamp new tokens; returns the requests finished since last time."""
        for en in sched.active.values():
            self.note(en.st, now)
        finished = sched.done[self.done_seen:]
        self.done_seen = len(sched.done)
        for st in finished:
            self.note(st, now)
        return finished


def _percentile(values, q) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def drive(spec: dict, seed: int, seconds: float, trace: bool, *,
          t_start: float, peaks: dict, monitor, log) -> dict:
    """One run of the cell.  The result's ``control`` reads the float8
    control on the same sample, scored as the program is
    (``bench/tools.py control``)."""
    import repro.serving.engine as engine_mod
    from repro.serving.scheduler import Request, Scheduler, _Entry

    config, traffic = spec["config"], spec["traffic"]
    serve = traffic["serve"]
    m = reference.model_dims(config["config"])
    arch = program_arch(config)
    items = traffic_mod.schedule(traffic, seed, seconds, m["vocab"])
    max_len = traffic_mod.max_request_tokens(traffic)
    page_size = int(serve["page_size"])
    num_pages = int(serve["kv_pool_tokens"]) // page_size + 1
    dev = jax.devices()[0]

    made = []

    def bench_weights(table, _rng):
        w = weights_mod.make_weights(m, seed, config["served_dtype"])
        weights_mod.check_layout(w, table)
        made.append(w)
        return w

    t0 = time.perf_counter()
    with mock.patch.object(engine_mod, "init_params", bench_weights):
        engine = engine_mod.ServeEngine(
            arch=arch, num_slots=int(serve["slots"]), max_len=max_len,
            seed=seed % (1 << 31), kv_layout="paged", page_size=page_size,
            num_pages=num_pages, kv_kernel="auto", log=log)
    weights = made[0]
    jax.block_until_ready(weights)
    log(f"engine: arch={arch} slots={engine.num_slots} max_len="
        f"{engine.max_len} pages={engine.num_pages} page_size="
        f"{engine.page_size} chunk={engine.prefill_chunk} kv_kernel="
        f"{engine.kv_kernel} weights_s={time.perf_counter() - t0:.3f}")
    pool = engine.make_pool()
    rec = Recorder()

    def decode_fn(cache, tokens, active, *extras):
        if rec.tracing:
            rec.decode_live.append(
                [int(pool.lengths[s]) + 1 for s in sched.active])
        with _annotate("bench.decode"):
            logits, new_cache = engine.decode_fn(cache, tokens, active,
                                                 *extras)
        rec.tops.append((top_logit(logits),
                         [(slot, en.st.rid, len(en.st.tokens))
                          for slot, en in sched.active.items()]))
        return logits, new_cache

    def chunk_fn(cache, tokens, slot, offset, n_valid, *extras):
        with _annotate("bench.chunk"):
            return engine.chunk_fn(cache, tokens, slot, offset, n_valid,
                                   *extras)

    sched = Scheduler(pool, engine.prefill_fn, decode_fn, eos_id=None,
                      policy="continuous", sampler=engine.sampler,
                      clock=time.perf_counter, chunk_step_fn=chunk_fn,
                      prefill_chunk=engine.prefill_chunk,
                      prefill_chunk_unit=engine.chunk_unit,
                      vocab_size=engine.cfg.vocab_size)
    # greedy traffic takes the scheduler's argmax path, as Scheduler.run
    # decides for an all-greedy trace
    sched.all_greedy = True
    reqs = {it.rid: Request(rid=it.rid, prompt=it.prompt,
                            max_new_tokens=it.max_new_tokens) for it in items}
    sched.validate(list(reqs.values()))

    # -- warm-up: every program the traffic reaches, once -----------------
    t0 = time.perf_counter()
    # a preempted request resumes by prefilling its prompt and the tokens
    # it was served, so resumes reach up to max_len
    pairs = chunk_programs(int(traffic["prompt"]["min"]), max_len,
                           engine.prefill_chunk, pool.kv_bound_cap)
    slot = pool.alloc()
    pool.reserve_prefix(slot, max_len)
    for bucket, bound in pairs:
        toks = jnp.zeros((1, bucket), jnp.int32)
        logits, new_cache = engine.chunk_fn(
            pool.cache, toks, jnp.int32(slot), jnp.int32(bound - bucket),
            jnp.int32(bucket), bound, *pool.chunk_extras(slot))
        pool.adopt(new_cache)
        np.asarray(jnp.argmax(logits[:, -1], axis=-1))
    S = pool.num_slots
    for _ in range(2):
        logits, new_cache = engine.decode_fn(
            pool.cache, jnp.zeros((S, 1), jnp.int32),
            jnp.zeros((S,), jnp.int32), *pool.decode_extras())
        pool.update(new_cache, ())
        np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        top_logit(logits).block_until_ready()
    pool.free(slot)
    jax.block_until_ready(pool.cache)
    warm_programs = monitor.compiles
    log(f"warm-up: chunk programs={len(pairs)} {pairs} decode=1 "
        f"seconds={time.perf_counter() - t0:.3f} {monitor.line()}")

    closed = traffic["loop"] == "closed"
    next_item = 0
    due_abs: dict = {}
    clients = int(traffic.get("clients", 0))
    if closed:
        # the clients' first requests: admitted and prefilled in set-up
        for _ in range(clients):
            it = items[next_item]
            next_item += 1
            sched.queue.append(_Entry(reqs[it.rid]))
            due_abs[it.rid] = time.perf_counter()
        sched.admit_from_queue()
        ticks = 0
        while sched.queue or sched.prefill_backlog:
            sched.step()
            sched.admit_from_queue()
            rec.observe(sched, time.perf_counter())
            ticks += 1
            if ticks > CLOSED_SETUP_TICK_LIMIT:
                raise RuntimeError("closed-loop set-up did not converge")
        log(f"closed loop: {clients} clients prefilled in {ticks} ticks")

    compiles_before, hits_before = monitor.compiles, monitor.cache_hits
    t_open = time.perf_counter()
    t_end = t_open + seconds
    setup_s = t_open - t_start
    trace_dir = None
    tr0 = t_open + TRACE_START_SHARE * seconds
    tr1 = tr0 + min(TRACE_MAX_S, 0.5 * seconds)
    if trace:
        trace_dir = os.path.join(spec["root"], ".bench_out", "trace",
                                 f"{spec['cell']['name']}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    lateness = []
    ticks = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace and not rec.tracing and tr0 <= now < tr1:
            jax.profiler.start_trace(trace_dir)
            rec.tracing = True
        elif rec.tracing and now >= tr1:
            jax.profiler.stop_trace()
            rec.tracing = False
            trace = False
        with _annotate("bench.host"):
            if not closed:
                while next_item < len(items) and \
                        t_open + items[next_item].due <= now:
                    it = items[next_item]
                    next_item += 1
                    due_abs[it.rid] = t_open + it.due
                    lateness.append(now - due_abs[it.rid])
                    sched.queue.append(_Entry(reqs[it.rid]))
            sched.admit_from_queue()
        if sched.active or sched.prefill_backlog:
            with _annotate("bench.tick"):
                sched.step()
            ticks += 1
            now = time.perf_counter()
            with _annotate("bench.host"):
                finished = rec.observe(sched, now)
                if closed:
                    for _ in finished:
                        it = items[next_item % len(items)]
                        rid = it.rid + len(items) * (next_item // len(items))
                        next_item += 1
                        reqs[rid] = Request(rid=rid, prompt=it.prompt,
                                            max_new_tokens=it.max_new_tokens)
                        sched.queue.append(_Entry(reqs[rid]))
                        due_abs[rid] = now
        elif sched.queue:
            raise RuntimeError("requests are queued but none can be admitted "
                               "into an idle pool")
        else:
            wake = [t_end]
            if next_item < len(items):
                wake.append(t_open + items[next_item].due)
            if trace and not rec.tracing and now < tr0:
                wake.append(tr0)
            if rec.tracing:
                wake.append(tr1)
            with _annotate("bench.wait_arrival"):
                time.sleep(max(0.0, min(wake) - now))
    if rec.tracing:
        jax.profiler.stop_trace()
        rec.tracing = False
    t_close = time.perf_counter()
    in_window_compiles = monitor.compiles - compiles_before
    in_window_programs = in_window_compiles + monitor.cache_hits - hits_before
    stats = sched.stats()
    memory_peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    # -- end to end ---------------------------------------------------------
    ttft, gaps, tokens_in_window = [], [], 0
    for rid, due in due_abs.items():
        times = rec.times.get(rid, [])
        if not closed and t_open <= due < t_end:
            first = times[0] if times else None
            ttft.append(((first if first is not None and first < t_end
                          else t_end) - due) * 1e3)
        for a, b in zip(times, times[1:]):
            if t_open <= b < t_end:
                gaps.append((b - a) * 1e3)
        tokens_in_window += sum(1 for t in times if t_open <= t < t_end)
    done_in_window = sum(1 for st in sched.done
                         if t_open <= rec.times[st.rid][-1] < t_end)
    attempted = sum(1 for d in due_abs.values() if d < t_end)
    e2e = {"setup_s": setup_s,
           "output_tokens_per_s": tokens_in_window / seconds}
    if ttft:
        e2e["ttft_p90_ms"] = _percentile(ttft, 90)
    if gaps:
        e2e["itl_p99_ms"] = _percentile(gaps, 99)
    log(f"window: seconds={seconds} closed_after={t_close - t_open:.3f} "
        f"ticks={ticks} attempted={attempted} completed={done_in_window} "
        f"offered_per_s={attempted / seconds:.4f} "
        f"completed_per_s={done_in_window / seconds:.4f} "
        f"tokens={tokens_in_window} queue_at_close={len(sched.queue)} "
        f"in_flight_at_close={sched.in_flight} "
        f"compiles_in_window={in_window_compiles} "
        f"programs_in_window={in_window_programs} "
        f"programs_after_warmup={compiles_before - warm_programs}")
    log(f"ttft_ms: n={len(ttft)} p50={_percentile(ttft, 50)} "
        f"p90={_percentile(ttft, 90)} max={max(ttft) if ttft else None}")
    log(f"itl_ms: n={len(gaps)} p50={_percentile(gaps, 50)} "
        f"p99={_percentile(gaps, 99)}")
    log(f"lateness_ms: n={len(lateness)} mean="
        f"{np.mean(lateness) * 1e3 if lateness else None} "
        f"max={max(lateness) * 1e3 if lateness else None}")
    log(f"serve stats: decode_steps={stats.decode_steps} "
        f"prefill_chunks={stats.prefill_chunks} "
        f"prefill_compiles={stats.prefill_compiles} "
        f"occupancy={stats.occupancy:.4f} preemptions={stats.preemptions} "
        f"peak_active={stats.peak_active} "
        f"peak_resident_tokens={stats.peak_resident_tokens}")
    log(f"memory: peak_bytes_in_use={memory_peak} "
        f"bytes_limit={(dev.memory_stats() or {}).get('bytes_limit')} "
        f"setup_s={setup_s:.3f}")

    # -- correctness --------------------------------------------------------
    finished = sorted(sched.done, key=lambda st: st.rid)
    bad = [st.rid for st in finished
           if len(st.tokens) != st.max_new_tokens
           or not all(0 <= t < m["vocab"] for t in st.tokens)]
    # requests still decoding at the close count with the tokens they
    # were served: a long-context window may finish few of its requests
    served = finished + sorted((en.st for en in sched.active.values()),
                               key=lambda st: st.rid)
    sample = _check_sample(served, reqs, seed,
                           int(traffic["check"]["tokens"]))
    top_of = rec.picked_from()
    tokens = [st.tokens for st in sample]
    tops = [[top_of.get((st.rid, i), np.nan) for i in range(len(st.tokens))]
            for st in sample]
    # the program's state goes before the reference runs beside the weights
    pool.cache = None
    del sched, pool, engine, decode_fn, chunk_fn
    gc.collect()
    t0 = time.perf_counter()
    seqs = [np.concatenate([reqs[st.rid].prompt,
                            np.asarray(st.tokens[:-1], np.int32)])
            for st in sample]
    rows = [reference.teacher_rows(len(reqs[st.rid].prompt), len(st.tokens))
            for st in sample]
    ref = reference.logits_at(weights, m, seqs, rows)
    got = reference.readings(ref, tokens, tops)
    n_checked = sum(len(t) for t in tokens)
    n_tops = sum(int(np.sum(~np.isnan(t))) for t in tops)
    limits = traffic["check"]["limits"]
    log(f"check: requests={len(sample)} served_tokens={n_checked} "
        f"decode_picks={n_tops} "
        f"longest={max((len(s) for s in seqs), default=0)} "
        f"readings={got} limits={limits} malformed={bad} "
        f"reference_s={time.perf_counter() - t0:.3f}")
    checked = _judge(got, limits)
    correct = bool(sample) and bool(checked) and not bad and all(
        v["value"] <= v["limit"] for v in checked.values())
    checked["malformed_requests"] = {"value": len(bad), "limit": 0}
    checked["programs_in_window"] = {"value": in_window_programs, "limit": 0}
    checked["served_tokens_compared"] = {
        "value": n_checked, "limit": int(traffic["check"]["tokens"])}
    correct = correct and in_window_programs == 0 and n_tops > 0 and \
        n_checked >= int(traffic["check"]["tokens"])

    def control() -> dict:
        """The float8 control in the program's place on the same sample,
        judged against the same limits."""
        picks, picked_from = reference.control_picks(weights, m, seqs, rows)
        c = reference.readings(ref, picks, picked_from)
        judged = _judge(c, limits)
        return {"readings": c, "checked": judged, "correct": all(
            v["value"] <= v["limit"] for v in judged.values())}

    out = {"end_to_end": e2e, "correct": correct, "attempted": attempted,
           "failed": 0, "memory_peak_bytes": memory_peak, "checked": checked,
           "readings": got, "control": control,
           "model": m, "peaks": peaks, "decode_live": rec.decode_live,
           "config": config, "traffic": traffic}
    if trace_dir is not None:
        events = load_events(trace_dir)
        out["reduced"] = reduce_events(events)
        out["events"] = events
        shutil.rmtree(trace_dir, ignore_errors=True)
        r = out["reduced"]
        log(f"trace: window_s={r['window_s']:.6f} busy_s={r['busy_s']:.6f} "
            f"idle_s={r['idle_s']:.6f} wait_s={r['wait_s']:.6f} "
            f"decode_calls={len(rec.decode_live)}")
        for name, v in sorted(r["modules"].items(),
                              key=lambda kv: -kv[1]["seconds"])[:12]:
            log(f"trace module {name!r}: {v}")
        for name, v in sorted(r["ops"].items(),
                              key=lambda kv: -kv[1]["seconds"])[:12]:
            log(f"trace op {name!r}: {v}")
        for name, v in r["annotations"].items():
            log(f"trace annotation {name}: {v}")
    return out


def _judge(readings: dict, limits: dict) -> dict:
    """The readings the cell compares, each beside its limit."""
    return {name: {"value": readings[name], "limit": limit}
            for name, limit in limits.items()}


def _check_sample(finished: list, reqs: dict, seed: int, want: int) -> list:
    """Served requests drawn by the seed until `want` served tokens are
    covered; the one with the longest sequence is always first."""
    if not finished:
        return []
    total = lambda st: len(reqs[st.rid].prompt) + len(st.tokens)  # noqa
    longest = max(finished, key=lambda st: (total(st), st.rid))
    rest = [st for st in finished if st is not longest]
    order = np.random.default_rng(seed ^ CHECK_SALT).permutation(len(rest))
    sample, n = [longest], len(longest.tokens)
    for i in order:
        if n >= want:
            break
        sample.append(rest[i])
        n += len(rest[i].tokens)
    return sample

