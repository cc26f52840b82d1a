"""Vstep-clocked telemetry: the MetricsRegistry schema both
``to_metrics()`` views are built on (keys can't drift from the
``router.py`` docstring table), Tracer span/ring semantics, the
bit-identity guarantee (tracing-on streams == tracing-off), Chrome-trace
export validity + byte determinism, AutoscaleEvent log replay, the
BENCH_serving.json structural validator, and the ``--trace-out`` /
``--metrics-out`` / ``--prom-out`` launcher flags."""

import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "benchmarks"))

import validate_bench  # noqa: E402
from repro.serving import (EVENT_KINDS, PHASES, ROUTER_SCHEMA, SERVE_SCHEMA,
                           AutoscaleEvent, AutoscalePolicy, MetricSpec,
                           MetricsRegistry, NGramDrafter, ReplicaRouter,
                           ServeEngine, Tracer, chrome_trace,
                           poisson_arrivals, prometheus_text,
                           replay_peak_replicas, sharedprefix_trace,
                           write_chrome_trace, zipf_trace)
from repro.serving import router as router_mod

ARCH = "picolm-4-smoke"

_ENGINES: dict = {}


def engine_for(layout="paged", page_size=8, num_pages=5, slots=3,
               max_len=64, spec_k=0):
    """Engines are expensive (jit); share them across tests by config."""
    key = (layout, page_size, num_pages, slots, max_len, spec_k)
    if key not in _ENGINES:
        _ENGINES[key] = ServeEngine(
            arch=ARCH, target="local:cpu", num_slots=slots, max_len=max_len,
            seed=0, kv_layout=layout, page_size=page_size,
            num_pages=num_pages, spec_k=spec_k, log=lambda *a, **k: None)
    return _ENGINES[key]


def _tokens(stats):
    return {r.rid: r.tokens for r in stats.results}


# ---------------------------------------------------------------------------
# MetricsRegistry: schema enforcement, instruments, Prometheus rendering


def test_registry_rejects_undeclared_and_incomplete():
    reg = MetricsRegistry((MetricSpec("a_total", "counter", "a"),
                           MetricSpec("b_now", "gauge", "b")))
    reg.set("a_total", 3)
    with pytest.raises(KeyError):
        reg.set("not_declared", 1)
    with pytest.raises(ValueError, match="b_now"):
        reg.snapshot()                      # declared b_now never set
    reg.set("b_now", 0.5)
    assert reg.snapshot() == {"a_total": 3, "b_now": 0.5}
    with pytest.raises(ValueError):
        reg.declare(MetricSpec("a_total", "counter", "dup"))
    with pytest.raises(ValueError):
        reg.declare(MetricSpec("bad key!", "gauge", ""))


def test_registry_template_keys_expand_per_replica():
    reg = MetricsRegistry(ROUTER_SCHEMA)
    for i in (0, 1, 7):
        reg.set(f"replica{i}_generated_tokens", i)
    assert reg.spec_for("replica7_occupancy").kind == "gauge"
    with pytest.raises(KeyError):
        reg.spec_for("replicaX_generated_tokens")
    snap = reg.snapshot(require_complete=False)
    assert snap["replica7_generated_tokens"] == 7


def test_registry_kind_discipline():
    reg = MetricsRegistry()
    reg.declare(MetricSpec("hits_total", "counter", ""))
    reg.declare(MetricSpec("depth_now", "gauge", ""))
    reg.inc("hits_total")
    reg.inc("hits_total", 2)
    with pytest.raises(ValueError):
        reg.inc("depth_now")
    with pytest.raises(ValueError):
        MetricSpec("lat_steps", "histogram", "")
    reg.set("depth_now", 4)
    assert reg.snapshot() == {"hits_total": 3, "depth_now": 4}


def test_prometheus_text_format():
    schema = (MetricSpec("x_total", "counter", "things done"),
              MetricSpec("y_now", "gauge", ""))
    text = prometheus_text({"x_total": 4, "y_now": float("nan"),
                            "z_free": 1.5}, schema)
    lines = text.splitlines()
    assert "# HELP x_total things done" in lines
    assert "# TYPE x_total counter" in lines
    assert "x_total 4" in lines
    assert "y_now NaN" in lines             # valid Prometheus literal
    assert "# TYPE z_free gauge" in lines   # undeclared key -> bare gauge
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# Satellite: both to_metrics() views are registry views over the schema


def test_serve_stats_to_metrics_matches_schema():
    eng = engine_for()
    reqs = zipf_trace(6, eng.cfg.vocab_size, max_prompt=16, max_new=6,
                      seed=0)
    m = eng.run(reqs, policy="continuous").to_metrics()
    assert list(m) == [s.key for s in SERVE_SCHEMA if "{i}" not in s.key]
    assert m["serve_requests_completed"] == 6
    assert all(v is not None for k, v in m.items()
               if not isinstance(v, float) or v == v)


def test_router_stats_to_metrics_matches_schema():
    eng = engine_for()
    reqs = zipf_trace(6, eng.cfg.vocab_size, max_prompt=16, max_new=6,
                      seed=0)
    router = ReplicaRouter([eng, eng], log=lambda *a, **k: None)
    m = router.run(reqs, policy="continuous").to_metrics()
    exact = [s.key for s in ROUTER_SCHEMA if "{i}" not in s.key]
    assert [k for k in m if not k.startswith("replica")] == exact
    reg = MetricsRegistry(ROUTER_SCHEMA)
    for i in range(2):
        for t in (s.key for s in ROUTER_SCHEMA if "{i}" in s.key):
            assert t.format(i=i) in m
    for key in m:                           # every key resolves in-schema
        reg.spec_for(key)


def _docstring_table_rows():
    """Parse the reST metric table out of router.py's module docstring."""
    doc = router_mod.__doc__
    rows = []
    in_table = seen_header = False
    for line in doc.splitlines():
        if re.fullmatch(r"=+(\s+=+)+", line.strip()):
            if in_table and seen_header:
                in_table = False            # closing rule
            elif in_table:
                seen_header = True          # rule under the header row
            else:
                in_table, seen_header = True, False
            continue
        if in_table and seen_header and line.strip():
            key, kind = line.split()[:2]
            rows.append((key, kind))
    return rows


def test_router_docstring_table_matches_schema():
    """The docstring's key table IS the export: same keys, same kinds,
    nothing missing, nothing extra (satellite: docs can't drift)."""
    rows = _docstring_table_rows()
    assert rows, "metric table not found in router.py docstring"
    assert len(rows) == len(ROUTER_SCHEMA)  # no duplicate rows either
    assert dict(rows) == {s.key: s.kind for s in ROUTER_SCHEMA}


# ---------------------------------------------------------------------------
# Tracer span/ring semantics


def test_tracer_span_matching_and_close():
    tr = Tracer()
    tr.begin("queued", 1, 0, replica=0)
    assert tr.end("queued", 1, 3, pending_tokens=8)
    (s,) = tr.spans_of("queued")
    assert (s.v_start, s.v_end, s.steps) == (0, 3, 3)
    assert s.attrs["pending_tokens"] == 8
    assert not tr.end("decode", 1, 4)       # never opened: counted, no crash
    assert tr.unmatched_ends == 1
    tr.begin("resume", 2, 5)
    assert tr.end_any(("resume", "queued"), 2, 7)
    tr.begin("decode", 3, 8)
    assert tr.close(10) == 1                # flushes the open decode span
    assert tr.spans_of("decode")[0].v_end == 10
    assert tr._open == {}


def test_tracer_rebegin_closes_old_and_ring_bounds():
    tr = Tracer(ring_capacity=4)
    tr.begin("decode", 9, 0)
    tr.begin("decode", 9, 5)                # re-begin same (rid, phase)
    first, second = tr.spans_of("decode")
    assert first.v_end == 5 and second.v_start == 5
    for v in range(10):
        tr.instant("preempt", v, replica=0, rid=v)
    assert tr.total_events == 10
    assert len(tr.events) == 4
    assert tr.dropped_events == 6
    assert [e.vstep for e in tr.events_of("preempt")] == [6, 7, 8, 9]
    with pytest.raises(ValueError):
        Tracer(ring_capacity=0)


# ---------------------------------------------------------------------------
# Keystone: tracing is observationally free, traces are byte-reproducible


def _full_fleet_run(tracer=None):
    """An openloop_poisson_autoscale-style drain that exercises every
    lifecycle phase: paged spec engines under page pressure (preempt +
    resume), chunked prefill, shared-prefix cache (cache_attach +
    reclaim), Poisson arrivals, SLO admission, autoscaling."""
    eng = engine_for(spec_k=2)
    reqs = sharedprefix_trace(10, eng.cfg.vocab_size, n_heads=2, head_len=8,
                              max_suffix=10, max_new=10, seed=3)
    # arrivals dense enough that the queue never empties before the third
    # replica is needed: at a mean gap of 2.0 vsteps the fleet drained
    # back to one replica first under some weight draws (the default PRNG
    # implementation changed between JAX releases), and never grew to 3
    reqs = poisson_arrivals(reqs, mean_gap=1.5, seed=7)
    router = ReplicaRouter([eng, eng, eng], log=lambda *a, **k: None)
    stats = router.run(reqs, policy="continuous", prefill_chunk=8,
                       prefix_cache=True, slo_ttft_steps=30,
                       slo_e2e_steps=200, admission="reject",
                       autoscale=AutoscalePolicy(min_replicas=1,
                                                 max_replicas=3),
                       tracer=tracer)
    return stats


def test_tracing_on_streams_bit_identical_to_off():
    baseline = _full_fleet_run(tracer=None)
    tr = Tracer()
    traced = _full_fleet_run(tracer=tr)
    assert _tokens(traced) == _tokens(baseline)
    assert traced.total_vsteps == baseline.total_vsteps
    wall = ("router_wall_s", "router_tokens_per_s")   # ADVISORY only
    strip = lambda m: {k: v for k, v in m.items() if k not in wall}
    assert strip(traced.to_metrics()) == strip(baseline.to_metrics())
    assert tr.spans                          # and it actually traced


def test_full_run_covers_every_phase_and_scales():
    tr = Tracer()
    stats = _full_fleet_run(tracer=tr)
    assert {s.phase for s in tr.spans} == set(PHASES)
    kinds = {e.kind for e in tr.events}
    assert "autoscale_grow" in kinds
    assert "preempt" in kinds
    assert kinds <= set(EVENT_KINDS)
    assert tr._open == {}                    # everything closed at drain end
    # spans carry the structured attributes the timeline reader needs
    chunk = tr.spans_of("prefill_chunk")[0]
    assert {"index", "tokens", "offset"} <= chunk.attrs.keys()
    verify = tr.spans_of("spec_verify")[0]
    assert {"k", "emitted", "accepted"} <= verify.attrs.keys()
    assert stats.peak_replicas >= 2


def test_chrome_trace_valid_and_byte_identical(tmp_path):
    tr1, tr2 = Tracer(), Tracer()
    _full_fleet_run(tracer=tr1)
    _full_fleet_run(tracer=tr2)
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    trace = write_chrome_trace(tr1, p1)
    write_chrome_trace(tr2, p2)
    assert p1.read_bytes() == p2.read_bytes()   # byte-identical runs
    data = json.loads(p1.read_text())           # valid JSON
    assert data == trace
    evs = data["traceEvents"]
    by_ph = {}
    for ev in evs:
        by_ph.setdefault(ev["ph"], []).append(ev)
    # >= 1 complete-event span per lifecycle phase
    names = {ev["name"] for ev in by_ph["X"]}
    assert set(PHASES) <= names
    # autoscale instants present, phase "i" with scope
    inst = [ev for ev in by_ph["i"] if ev["name"].startswith("autoscale_")]
    assert inst and all(ev["s"] == "p" for ev in inst)
    # metadata: one process per replica, slot threads + queue lane
    procs = [ev for ev in by_ph["M"] if ev["name"] == "process_name"]
    threads = [ev for ev in by_ph["M"] if ev["name"] == "thread_name"]
    assert {p["pid"] for p in procs} == {0, 1, 2}
    assert {t["args"]["name"] for t in threads} >= {"queue", "slot 0"}
    # vstep clock only: integer timestamps, no wall-clock anywhere
    assert all(isinstance(ev["ts"], int) for ev in evs if "ts" in ev)
    ts = [ev["ts"] for ev in by_ph["X"]]
    assert ts == sorted(ts)                     # monotone for Perfetto


# ---------------------------------------------------------------------------
# Satellite: the AutoscaleEvent log is deterministic and replayable


def test_autoscale_event_log_deterministic_and_replays_peak():
    s1 = _full_fleet_run()
    s2 = _full_fleet_run()
    assert s1.autoscale_events == s2.autoscale_events
    assert s1.autoscale_events, "autoscaler never acted — config regressed"
    assert replay_peak_replicas(s1.autoscale_events, 1) == s1.peak_replicas


def test_replay_peak_replicas_state_machine():
    import dataclasses
    ev = lambda action, r, serving: AutoscaleEvent(
        vstep=0, action=action, replica=r, serving=serving,
        per_replica_cap=4)
    log = [ev("grow", 1, 2), ev("grow", 2, 3), ev("drain", 2, 2),
           ev("stop", 2, 2), ev("drain", 1, 1)]
    assert replay_peak_replicas(log, 1) == 3
    assert replay_peak_replicas([], 2) == 2
    with pytest.raises(ValueError):          # serving count inconsistent
        replay_peak_replicas([ev("grow", 1, 9)], 1)
    bogus = dataclasses.replace(ev("grow", 1, 2), action="explode")
    with pytest.raises(ValueError):
        replay_peak_replicas([bogus], 1)


# ---------------------------------------------------------------------------
# Satellite: BENCH_serving.json structural validator


def _valid_bench():
    path = Path(__file__).parent.parent / "BENCH_serving.json"
    return validate_bench.parse_strict(path.read_text())


def test_validator_accepts_checked_in_bench():
    assert validate_bench.check(_valid_bench()) == []


def test_validator_flags_structural_drift():
    data = _valid_bench()
    data["cells"]["mystery_cell"] = {"tokens_per_s": 1.0}
    data["cells"]["paged_continuous"].pop("decode_steps")
    data["cells"]["paged_spec_on"]["surprise"] = 1
    data["cells"]["contiguous_static"]["tokens_per_step"] = "fast"
    del data["trace_seed"]
    problems = "\n".join(validate_bench.check(data))
    assert "mystery_cell" in problems
    assert "decode_steps" in problems
    assert "surprise" in problems
    assert "'fast'" in problems
    assert "trace_seed" in problems


def test_validator_rejects_nan_literals():
    with pytest.raises(ValueError, match="NaN"):
        validate_bench.parse_strict('{"cells": {"x": {"y": NaN}}}')
    assert validate_bench.parse_strict('{"y": null}') == {"y": None}


# ---------------------------------------------------------------------------
# Drafter instrumentation counters


def test_ngram_drafter_counts_hits_and_fallbacks():
    d = NGramDrafter(max_n=2)
    ctx = [1, 2, 3, 1, 2]
    d.draft(ctx, 3)                          # suffix (1,2) seen -> 3, ...
    assert d.calls == 1
    assert d.drafted_tokens == 3
    assert d.ngram_hits + d.fallbacks == 3
    assert d.ngram_hits >= 1
    d2 = NGramDrafter(max_n=3)
    d2.draft([7], 2)      # first token has no earlier suffix: fallback;
    assert d2.fallbacks == 1                 # then (7,7) -> period-1 hit
    assert d2.ngram_hits == 1


# ---------------------------------------------------------------------------
# Satellite: launcher flags write metrics / prometheus / trace files


def _launch(tmp_path, tag, **kw):
    from repro.launch.serve import serve_main
    paths = {k: tmp_path / f"{tag}_{k}.out" for k in
             ("trace_out", "metrics_out", "prom_out")}
    out = serve_main(arch=ARCH, batch=2, prefill_len=8, decode_tokens=4,
                     requests=3, max_len=32, seed=0,
                     log=lambda *a, **k: None,
                     **{k: str(p) for k, p in paths.items()}, **kw)
    return out, paths


@pytest.mark.parametrize("replicas,prefix", [(1, "serve_"), (2, "router_")])
def test_serve_main_telemetry_outputs(tmp_path, replicas, prefix):
    from repro.serving.telemetry import json_sanitize
    out, paths = _launch(tmp_path, f"x{replicas}", replicas=replicas)
    # metrics file: strict JSON (no NaN literals), matches the run's view
    metrics = validate_bench.parse_strict(paths["metrics_out"].read_text())
    assert any(k.startswith(prefix) for k in metrics)
    assert metrics[f"{prefix}requests_completed"] == 3
    assert metrics == json_sanitize(out["metrics"])
    prom = paths["prom_out"].read_text()
    assert f"# TYPE {prefix}requests_completed counter" in prom
    trace = json.loads(paths["trace_out"].read_text())
    assert {ev["name"] for ev in trace["traceEvents"]
            if ev["ph"] == "X"} >= {"queued", "decode"}


def test_serve_main_profile_dir(tmp_path):
    """--profile-dir: the drain's scheduler spans land in a profiler
    trace, and serve.step carries the vstep that --trace-out keys on."""
    import glob

    from jax.profiler import ProfileData
    _launch(tmp_path, "prof", profile_dir=str(tmp_path / "prof"))
    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [ev for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events]
    names = {ev.name for ev in events}
    assert {"serve.step", "serve.admit", "serve.page", "serve.decode",
            "serve.pick", "serve.finish"} <= names
    vsteps = [dict(ev.stats)["vstep"] for ev in events
              if ev.name == "serve.step"]
    assert vsteps[0] == 0 and vsteps == sorted(set(vsteps))
    # one clock: the last request's decode span in the vstep file ends
    # with the last tick's decode step, one vstep after its serve.step
    trace = json.loads((tmp_path / "prof_trace_out.out").read_text())
    ends = [ev["ts"] + ev["dur"] for ev in trace["traceEvents"]
            if ev.get("name") == "decode"]
    assert max(ends) == vsteps[-1] + 1
