"""The program's own names in a profiler trace: its host spans and the
scopes of its device ops.

``trace_reduce.load_events`` keeps the benchmark's ``bench.*`` annotations
and each op's bare name.  This module reads the same ``.xplane.pb`` and
keeps what the serving program names itself:

* host spans whose names start ``serve.`` (``serving/telemetry.span``),
  with their arguments (``vstep`` on ``serve.step``, ``rid`` on
  ``serve.chunk``, ...);
* each device op's scope path: the ``jax.named_scope`` names of
  ``SCOPES`` in the op's ``op_name`` metadata, outermost first
  (``layers/kv_write``); an op with none is ``(unscoped)``.  A TPU
  trace's op events carry no op_name (their stats are the device offset
  and duration), so it is read from the compiled program's HLO text
  (``metadata={op_name=...}``), keyed by the instruction name the trace
  shows: ``compiled.as_text()``, or XLA's dump
  (``--xla_dump_to=DIR --xla_dump_hlo_as_text``).

Events are ``(plane, line, name, start_ns, dur_ns, info)``: ``info`` is a
span's arguments or ``{"scope": path}`` for an op.  ``plain`` drops
``info``, which gives ``trace_reduce.reduce_events`` its input: there the
``serve.*`` spans nest inside ``bench.tick`` and ``bench.host`` and label
the idle gaps under them, while every number the readers of
``bench/readers.py`` take stays as it was.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from bench import trace_reduce as tr

PROGRAM_PREFIX = "serve."
SCOPES = ("embed", "layers", "qkv", "kv_write", "attn", "out_proj", "mlp",
          "logits")
UNSCOPED = "(unscoped)"
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                       r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scope_path(op_name: str) -> str:
    """``jit(decode_step)/jit(main)/layers/while/body/kv_write/scatter``
    -> ``layers/kv_write``."""
    parts = [p for p in op_name.split("/") if p in SCOPES]
    return "/".join(parts) or UNSCOPED


def hlo_op_names(text: str) -> dict:
    """Instruction name -> op_name metadata, over every computation of one
    HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def hlo_dump(hlo_dir: str) -> list:
    """The optimized HLO texts of an XLA dump directory."""
    texts = []
    for path in sorted(glob.glob(os.path.join(
            hlo_dir, "*after_optimizations.txt"))):
        with open(path) as f:
            texts.append(f.read())
    return texts


def _plain_value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def load_events(trace_dir: str, hlo_texts=()) -> list:
    """Events of the newest ``.xplane.pb`` under `trace_dir`: device ops
    and programs, and the host spans of the benchmark and of the program
    with their arguments.  An op of a program whose HLO text is among
    `hlo_texts` gets its scope."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = tr.is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(
                        (tr.ANNOTATION_PREFIX, PROGRAM_PREFIX)):
                    continue
                info = {} if device else {
                    k: _plain_value(v) for k, v in dict(ev.stats).items()}
                events.append((plane.name, line.name, tr.op_name(name),
                               float(ev.start_ns), float(ev.duration_ns),
                               info))
    hlo = {}
    for text in hlo_texts:
        m = re.match(r"HloModule\s+([\w.\-]+)", text)
        if m:
            hlo.setdefault(m.group(1), {}).update(hlo_op_names(text))
    if hlo:
        _scopes_from_hlo(events, hlo)
    return events


def _scopes_from_hlo(events: list, hlo: dict) -> None:
    """Give each op the scope its program's HLO text names (`hlo`: module
    name -> instruction name -> op_name); an op belongs to the program
    running on its device when it starts."""
    calls: dict = {}
    for p, ln, n, s, d, _ in events:
        if ln == tr.MODULES_LINE:
            calls.setdefault(p, []).append((s, s + d, n.split("(")[0]))
    for v in calls.values():
        v.sort()
    starts = {p: [c[0] for c in v] for p, v in calls.items()}
    for p, ln, n, s, d, info in events:
        if ln != tr.OPS_LINE or p not in calls:
            continue
        i = bisect.bisect_right(starts[p], s) - 1
        if i >= 0 and s < calls[p][i][1]:
            meta = hlo.get(calls[p][i][2], {}).get(n)
            if meta:
                info["scope"] = scope_path(meta)


def plain(events: list) -> list:
    """The events as ``trace_reduce`` takes them."""
    return [e[:5] for e in events]


def _nest(items: list) -> list:
    """Parent index of each (start, end) interval of one timeline, sorted
    by start then longest first (-1 for a top-level one)."""
    parent, stack = [], []
    for i, (s, e) in enumerate(items):
        while stack and items[stack[-1]][1] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


def self_times(ops: list) -> list:
    """Each op's device time less that of the ops nested in it (a TPU
    trace nests a loop's body ops inside the loop op)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = _nest([(s, e) for _, s, e, _ in ops])
    own = [e - s for _, s, e, _ in ops]
    for i, p in enumerate(parent):
        if p >= 0:
            own[p] -= ops[i][2] - ops[i][1]
    return [(n, scope, t) for (n, _, _, scope), t in zip(ops, own)]


def scopes(events: list, module: str = "jit_decode_step") -> dict | None:
    """Device time per call of the programs whose name holds `module`,
    split by the scope of the ops that ran inside their intervals on
    device 0 (each op's own time, its nested ops' left out).  ``None``
    when no such program ran or no op carries a scope."""
    dev = sorted({e[0] for e in events if tr.is_device_plane(e[0])})
    if not dev:
        return None
    dev = dev[0]
    calls = sorted((e[3], e[3] + e[4]) for e in events
                   if e[0] == dev and e[1] == tr.MODULES_LINE
                   and module in e[2])
    if not calls:
        return None
    starts = [s for s, _ in calls]
    ops = []
    for p, ln, n, s, d, info in events:
        if p != dev or ln != tr.OPS_LINE:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < calls[i][1]:
            ops.append((n, s, s + d, info.get("scope")))
    if not any(scope for *_, scope in ops):
        return None
    per_scope: dict = {}
    per_op: dict = {}
    for n, scope, t in self_times(ops):
        scope = scope or UNSCOPED
        per_scope[scope] = per_scope.get(scope, 0.0) + t
        o = per_op.setdefault(n, {"scope": scope, "seconds": 0.0})
        o["seconds"] += t
    n = len(calls)
    return {
        "module": module, "calls": n,
        "seconds_per_call": sum(e - s for s, e in calls) * 1e-9 / n,
        "scopes": {k: v * 1e-9 / n for k, v in sorted(
            per_scope.items(), key=lambda kv: -kv[1])},
        "ops": {k: {"scope": v["scope"], "seconds": v["seconds"] * 1e-9 / n}
                for k, v in sorted(per_op.items(),
                                   key=lambda kv: -kv[1]["seconds"])},
    }


def phases(events: list) -> dict:
    """Per host span name: count, host seconds, device-idle seconds under
    the span, and the part of that idle time under none of its child
    spans (``self_idle_s``).  The device is device 0."""
    dev = sorted({e[0] for e in events if tr.is_device_plane(e[0])})[0]
    busy = tr.union([(s, s + d) for p, ln, _, s, d, _ in events
                     if p == dev and ln == tr.OPS_LINE])
    busy_starts = [a for a, _ in busy]
    out: dict = {}
    lines = sorted({(p, ln) for p, ln, *_ in events
                    if not tr.is_device_plane(p)})
    for key in lines:
        spans = sorted(((s, s + d, n) for p, ln, n, s, d, _ in events
                        if (p, ln) == key), key=lambda x: (x[0], -x[1]))
        parent = _nest([(s, e) for s, e, _ in spans])
        idle = [(e - s) - tr.covered(busy, s, e, busy_starts)
                for s, e, _ in spans]
        own = list(idle)
        for i, p in enumerate(parent):
            if p >= 0:
                own[p] -= idle[i]
        for (s, e, n), i, o in zip(spans, idle, own):
            c = out.setdefault(n, {"count": 0, "seconds": 0.0,
                                   "idle_s": 0.0, "self_idle_s": 0.0})
            c["count"] += 1
            c["seconds"] += (e - s) * 1e-9
            c["idle_s"] += i * 1e-9
            c["self_idle_s"] += o * 1e-9
    return out


# -- readers: per-layer numbers from the program's spans and scopes --------

def step_host_ms(rec):
    """Mean host time per ``serve.step`` span with no op on the device."""
    step = rec["reduced"]["annotations"].get(PROGRAM_PREFIX + "step")
    if not step or not step["count"]:
        return None
    return step["host_only_s"] / step["count"] * 1e3


def decode_kv_write_ms(rec):
    """Device ms per decode step in ops under the ``kv_write`` scope."""
    sc = rec.get("scopes")
    if not sc:
        return None
    return sum(t for path, t in sc["scopes"].items()
               if "kv_write" in path.split("/")) * 1e3
