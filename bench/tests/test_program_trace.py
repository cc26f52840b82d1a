"""The program's spans and scopes in a trace: the spans one scheduler tick
emits on the CPU, the readers' numbers unchanged with the spans in the
trace, the scope reduction on a hand-made trace, and the recorded chip
traces with the spans (``data/spans/``)."""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from bench import program_trace as pt
from bench import readers, reference, trace_reduce as tr
from bench.work import peaks_for

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
CELLS = ["deepseek-7b.chat", "stablelm-2-1.6b.longctx"]


def ev(plane, line, name, start, end, info=None):
    return (plane, line, name, float(start), float(end - start), info or {})


def _nested(events, outer, inner):
    """Whether every `inner` span lies inside some `outer` span."""
    outs = [(s, s + d) for *_, n, s, d, _ in events if n == outer]
    return all(any(a <= s and s + d <= b for a, b in outs)
               for *_, n, s, d, _ in events if n == inner)


def test_scheduler_step_spans(tmp_path):
    from repro.serving import ServeEngine
    from repro.serving.scheduler import Request, Scheduler, _Entry
    eng = ServeEngine(arch="picolm-4-smoke", target="local:cpu",
                      num_slots=2, max_len=64, seed=0, kv_layout="paged",
                      page_size=8, num_pages=12, prefill_chunk=8,
                      log=lambda *a, **k: None)
    sched = Scheduler(eng.make_pool(), eng.prefill_fn, eng.decode_fn,
                      chunk_step_fn=eng.chunk_fn, prefill_chunk=8,
                      vocab_size=eng.cfg.vocab_size)
    sched.all_greedy = True
    sched.queue.append(_Entry(Request(
        rid=7, prompt=np.arange(1, 13, dtype=np.int32), max_new_tokens=3)))
    with jax.profiler.trace(str(tmp_path)):
        sched.admit_from_queue()
        while sched.active or sched.prefill_backlog:
            sched.step()
    events = pt.load_events(str(tmp_path))
    spans = [e for e in events if e[2].startswith(pt.PROGRAM_PREFIX)]
    names = [e[2] for e in sorted(spans, key=lambda e: (e[3], -e[4]))]
    # admission, then two ticks of chunks (the second ends in the first
    # token's pick), then decode ticks: page, decode, pick, finish
    assert names[:4] == ["serve.admit", "serve.step", "serve.prefill",
                         "serve.chunk"]
    assert names[4:13] == ["serve.step", "serve.prefill", "serve.chunk",
                           "serve.pick", "serve.page", "serve.decode",
                           "serve.pick", "serve.finish", "serve.step"]
    for inner in ("prefill", "page", "decode", "pick", "finish"):
        assert _nested(spans, "serve.step", "serve." + inner)
    assert _nested(spans, "serve.prefill", "serve.chunk")
    info = {e[2]: e[5] for e in reversed(spans)}
    assert info["serve.admit"] == {"admitted": 1}
    assert info["serve.step"] == {"vstep": 0, "active": 0}
    assert info["serve.chunk"] == {"rid": 7, "slot": 0, "tokens": 8,
                                   "bucket": 8, "bound": 8}
    assert [e[5].get("rid") for e in spans if e[2] == "serve.pick"] \
        == [7, None, None]
    vsteps = [e[5]["vstep"] for e in spans if e[2] == "serve.step"]
    assert vsteps == sorted(vsteps) and len(vsteps) == 3
    assert sched.done[0].tokens and len(sched.done[0].tokens) == 3


def _with_serve_spans(events):
    """Synthetic program spans nested in the recorded ``bench.*`` ones:
    in each tick a ``serve.step`` split into decode, pick and finish, and
    a ``serve.admit`` in each host stretch."""
    out = [e + ({},) for e in events]
    for p, ln, n, s, d in events:
        if n == "bench.tick":
            a, b = s + 1.0, s + d - 1.0
            cut1, cut2 = a + 0.3 * (b - a), a + 0.8 * (b - a)
            out += [ev(p, ln, "serve.step", a, b, {"vstep": 1}),
                    ev(p, ln, "serve.decode", a, cut1),
                    ev(p, ln, "serve.pick", cut1, cut2),
                    ev(p, ln, "serve.finish", cut2, b)]
        elif n == "bench.host":
            out.append(ev(p, ln, "serve.admit", s + 0.5, s + d - 0.5))
    return out


def _rec(reduced, config):
    m = reference.model_dims(config)
    n, _ = tr.module_time(reduced, readers.DECODE)
    return {"reduced": reduced, "model": m, "peaks": peaks_for("TPU v5 lite"),
            "decode_live": [[300 + 40 * i, 90] for i in range(max(n, 1))]}


READERS = ("host_ms_per_tick", "prefill_chunk_ms", "decode_step_ms",
           "decode_mfu", "paged_attn_roofline", "idle_share")


@pytest.mark.parametrize("cell", CELLS)
def test_serve_spans_leave_readers_unchanged(cell):
    import json
    config = json.loads((CONFIGS / f"{cell.rsplit('.', 1)[0]}.json").read_text())
    events = tr.read_events(str(DATA / f"{cell}.events.json.gz"))
    spanned = _with_serve_spans(events)
    before = tr.reduce_events(events)
    after = tr.reduce_events(pt.plain(spanned))
    for name in READERS:
        read = getattr(readers, name)
        assert read(_rec(after, config["config"])) == \
            read(_rec(before, config["config"])), name
    for key in ("window_s", "busy_s", "idle_s", "wait_s", "modules", "ops"):
        assert after[key] == before[key], key
    # the gaps under a tick are now named by the program's span over them
    assert "bench.tick" in {n for n, _ in before["breakdown"]["idle_gaps"]}
    labels = {n for n, _ in after["breakdown"]["idle_gaps"]}
    assert "bench.tick" not in labels
    assert labels & {"serve.decode", "serve.pick", "serve.finish"}
    step = after["annotations"]["serve.step"]
    assert pt.step_host_ms({"reduced": after}) == pytest.approx(
        step["host_only_s"] / step["count"] * 1e3)


def _hand_made():
    """Two decode steps (a loop op whose body ops nest inside it, a copy
    with no scope) and one chunk step, under host spans."""
    ops = tr.OPS_LINE
    events = []
    for t in (0, 100):
        events += [
            ev(DEV, tr.MODULES_LINE, "jit_decode_step(1)", t, t + 40),
            ev(DEV, ops, "fusion.1", t, t + 4, {"scope": "embed"}),
            ev(DEV, ops, "while.2", t + 4, t + 34, {"scope": "layers"}),
            ev(DEV, ops, "fusion.3", t + 4, t + 10, {"scope": "layers/qkv"}),
            ev(DEV, ops, "scatter.4", t + 10, t + 13,
               {"scope": "layers/kv_write"}),
            ev(DEV, ops, "paged_attention.5", t + 13, t + 30,
               {"scope": "layers/attn"}),
            ev(DEV, ops, "copy.6", t + 34, t + 38),
            ev(DEV, ops, "fusion.7", t + 38, t + 40, {"scope": "logits"}),
        ]
    events += [
        ev(DEV, tr.MODULES_LINE, "jit_chunk_step(2)", 50, 70),
        ev(DEV, ops, "scatter.4", 50, 70, {"scope": "layers/kv_write"}),
        ev(HOST, "python3", "serve.step", 40, 100, {"vstep": 1}),
        ev(HOST, "python3", "serve.prefill", 45, 75),
        ev(HOST, "python3", "serve.chunk", 46, 50, {"rid": 3}),
        ev(HOST, "python3", "serve.decode", 80, 95),
        ev(HOST, "python3", "serve.step", 100, 145, {"vstep": 2}),
        ev(HOST, "python3", "serve.pick", 140, 145),
    ]
    return events


def test_scopes_hand_made():
    events = _hand_made()
    sc = pt.scopes(events)
    assert sc["calls"] == 2
    assert sc["seconds_per_call"] == pytest.approx(40e-9)
    # the loop's own time is what its body ops leave of it: 30 - 26
    assert sc["scopes"] == {
        "layers/attn": pytest.approx(17e-9), "layers/qkv": pytest.approx(6e-9),
        "embed": pytest.approx(4e-9), "layers": pytest.approx(4e-9),
        "(unscoped)": pytest.approx(4e-9),
        "layers/kv_write": pytest.approx(3e-9),
        "logits": pytest.approx(2e-9)}
    assert sum(sc["scopes"].values()) == pytest.approx(40e-9)
    assert sc["ops"]["copy.6"] == {"scope": "(unscoped)",
                                   "seconds": pytest.approx(4e-9)}
    assert pt.decode_kv_write_ms({"scopes": sc}) == pytest.approx(3e-6)
    assert pt.scopes(events, "jit_chunk_step")["scopes"] == {
        "layers/kv_write": pytest.approx(20e-9)}
    # a trace with no scope, or no decode step, has nothing to read
    bare = [e[:5] + ({},) for e in events]
    assert pt.scopes(bare) is None
    assert pt.scopes(events, "jit_verify_step") is None
    assert pt.decode_kv_write_ms({}) is None


def test_phases_hand_made():
    ph = pt.phases(_hand_made())
    # device busy: [0, 40), [50, 70), [100, 140)
    assert ph["serve.step"]["count"] == 2
    assert ph["serve.step"]["seconds"] == pytest.approx(105e-9)
    assert ph["serve.step"]["idle_s"] == pytest.approx(45e-9)
    # prefill idles [45, 50) and [70, 75), decode [80, 95), pick [140, 145)
    assert ph["serve.prefill"]["idle_s"] == pytest.approx(10e-9)
    assert ph["serve.prefill"]["self_idle_s"] == pytest.approx(6e-9)
    assert ph["serve.chunk"]["idle_s"] == pytest.approx(4e-9)
    assert ph["serve.decode"]["idle_s"] == pytest.approx(15e-9)
    assert ph["serve.pick"]["idle_s"] == pytest.approx(5e-9)
    assert ph["serve.step"]["self_idle_s"] == pytest.approx(15e-9)
    assert sum(v["self_idle_s"] for v in ph.values()) == pytest.approx(
        ph["serve.step"]["idle_s"])


def test_hlo_op_names():
    text = """HloModule jit_decode_step, is_scheduled=true

%body (p: bf16[4]) -> bf16[4] {
  %scatter.4 = bf16[8]{0} scatter(%a, %b, %c), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/kv_write/scatter" stack_frame_id=2}
  ROOT %copy.6 = bf16[4]{0} copy(%p)
}

ENTRY %main {
  ROOT %while.2 = (bf16[4]) while(%t), body=%body, metadata={op_name="jit(decode_step)/layers/while"}
}
"""
    names = pt.hlo_op_names(text)
    assert names == {
        "scatter.4": "jit(decode_step)/layers/while/body/closed_call/"
                     "kv_write/scatter",
        "while.2": "jit(decode_step)/layers/while"}
    assert pt.scope_path(names["scatter.4"]) == "layers/kv_write"
    assert pt.scope_path(names["while.2"]) == "layers"
    assert pt.scope_path("jit(decode_step)/jit(_where)/select_n") == \
        pt.UNSCOPED
    # an op takes the scope its own program's HLO names: the chunk step's
    # scatter.4 is another instruction than the decode step's
    events = [ev(DEV, tr.MODULES_LINE, "jit_decode_step(1)", 0, 10),
              ev(DEV, tr.OPS_LINE, "scatter.4", 1, 2),
              ev(DEV, tr.MODULES_LINE, "jit_chunk_step(2)", 10, 20),
              ev(DEV, tr.OPS_LINE, "scatter.4", 11, 12)]
    pt._scopes_from_hlo(events, {"jit_decode_step": names})
    assert [e[5] for e in events] == [{}, {"scope": "layers/kv_write"},
                                      {}, {}]


@pytest.mark.parametrize("cell", CELLS)
def test_recorded_program_trace(cell):
    """A stretch of a traced chip run with the program's spans and
    scopes: the program's metrics read, the readers' numbers stay, and
    the spans nest inside the benchmark's annotations."""
    events = tr.read_events(str(DATA / "spans" / f"{cell}.spans.json.gz"))
    with_spans = tr.reduce_events(pt.plain(events))
    without = tr.reduce_events([e[:5] for e in events
                                if not e[2].startswith(pt.PROGRAM_PREFIX)])
    for key in ("busy_s", "idle_s", "wait_s", "window_s", "modules"):
        assert with_spans[key] == without[key], key
    assert with_spans["annotations"]["bench.tick"] == \
        without["annotations"]["bench.tick"]
    rec = {"reduced": with_spans, "scopes": pt.scopes(events)}
    assert pt.step_host_ms(rec) > 0
    assert pt.decode_kv_write_ms(rec) > 0
    assert _nested(events, "bench.tick", "serve.step")
    waits = [(s, s + d) for *_, n, s, d, _ in events
             if n == "bench.wait_arrival"]
    assert not [e for e in events if e[2].startswith(pt.PROGRAM_PREFIX)
                and any(a <= e[3] < b for a, b in waits)]
    ph = pt.phases(events)
    step = ph["serve.step"]
    assert 0 <= step["self_idle_s"] <= step["idle_s"] <= step["seconds"]
