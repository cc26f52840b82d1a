"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

``load_events`` reads the ``.xplane.pb`` the profiler wrote into a list of
plain events ``(plane, line, name, start_ns, dur_ns)``: the device's
operations and programs, and the benchmark's host annotations (names
starting ``bench.``).  ``reduce_events`` works on that list alone, so a
test can check it on a trace recorded once on the chip:

* device busy time: the union of the intervals of every device op,
  clipped to the traced stretch;
* idle gaps: the stretches with no op, cut where an annotation opens or
  closes, each piece labelled by the innermost benchmark annotation open
  over it (``bench.wait_arrival`` marks the host waiting for a request to
  fall due: no work, so not idle);
* device time and count per program (``XLA Modules``) and per op name;
* per annotation: count, host time, and host time with no device op.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os

ANNOTATION_PREFIX = "bench."
WAIT = "bench.wait_arrival"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
# annotations nest at most this deep; a lookup scans no further back
NEST_DEPTH = 16


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def op_name(text: str) -> str:
    """An op's own name: the TPU trace names an op by its whole HLO line,
    ``%paged_attention.6 = bf16[...] custom-call(...)``, whose operands
    name other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def load_events(trace_dir: str) -> list:
    """Events of the newest ``.xplane.pb`` under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                events.append((plane.name, line.name, op_name(ev.name),
                               float(ev.start_ns), float(ev.duration_ns)))
    return events


def save_events(events: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def read_events(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list, s: float, e: float, starts: list | None = None
            ) -> float:
    """Length of [s, e) covered by the disjoint sorted `merged` (`starts`:
    their start points, for the search)."""
    starts = starts if starts is not None else [a for a, _ in merged]
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        a, b = merged[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


def reduce_events(events: list, window: tuple | None = None) -> dict:
    """The reduction, on device 0's timeline (one-chip cells).  `window`
    is the traced stretch ``(start_ns, end_ns)``; by default it spans the
    benchmark's annotations."""
    planes = sorted({p for p, *_ in events if is_device_plane(p)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    dev = planes[0]
    ops = [(n, s, s + d) for p, ln, n, s, d in events
           if p == dev and ln == OPS_LINE]
    modules = [(n, s, s + d) for p, ln, n, s, d in events
               if p == dev and ln == MODULES_LINE]
    notes = [(n, s, s + d) for p, ln, n, s, d in events
             if not is_device_plane(p)]
    if not ops:
        raise ValueError(f"no op ran on {dev} in the trace")
    if window is None:
        ends = [e for _, _, e in notes] or [e for _, _, e in ops]
        starts = [s for _, s, _ in notes] or [s for _, s, _ in ops]
        window = (min(starts), max(ends))
    w0, w1 = window
    busy = union([(max(s, w0), min(e, w1)) for _, s, e in ops
                  if e > w0 and s < w1])
    busy_ns = sum(e - s for s, e in busy)

    notes.sort(key=lambda x: x[1])
    note_starts = [s for _, s, _ in notes]

    def open_note(t):
        """The innermost annotation open at t: the latest-starting one
        that has not ended (annotations nest)."""
        i = bisect.bisect_right(note_starts, t) - 1
        stop = max(i - NEST_DEPTH, -1)
        while i > stop:
            n, s, e = notes[i]
            if e > t:
                return n
            i -= 1
        return "(none)"

    note_ends = sorted(e for _, _, e in notes)

    def pieces(a, b):
        """[a, b) cut where an annotation starts or ends, each piece
        labelled by the innermost annotation open over it."""
        cuts = {a, b}
        for arr in (note_starts, note_ends):
            i = bisect.bisect_right(arr, a)
            while i < len(arr) and arr[i] < b:
                cuts.add(arr[i])
                i += 1
        cuts = sorted(cuts)
        return [(open_note(x), x, y) for x, y in zip(cuts, cuts[1:])]

    gaps = []
    cursor = w0
    for s, e in busy + [(w1, w1)]:
        if s > cursor:
            gaps.extend(pieces(cursor, s))
        cursor = max(cursor, e)
    wait_ns = sum(e - s for n, s, e in gaps if n == WAIT)
    idle_ns = sum(e - s for n, s, e in gaps if n != WAIT)

    per_module: dict = {}
    for n, s, e in modules:
        c = per_module.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += e - s
    per_op: dict = {}
    for n, s, e in ops:
        c = per_op.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += e - s
    busy_starts = [a for a, _ in busy]
    per_note: dict = {}
    for n, s, e in notes:
        c = per_note.setdefault(n, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += e - s
        c[2] += (e - s) - covered(busy, s, e, busy_starts)
    longest = sorted(gaps, key=lambda g: g[2] - g[1], reverse=True)
    top_ops = sorted(per_op.items(), key=lambda kv: kv[1][1], reverse=True)
    return {
        "device": dev,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_s": idle_ns * 1e-9,
        "wait_s": wait_ns * 1e-9,
        "modules": {n: {"count": c, "seconds": t * 1e-9}
                    for n, (c, t) in per_module.items()},
        "ops": {n: {"count": c, "seconds": t * 1e-9}
                for n, (c, t) in per_op.items()},
        "annotations": {n: {"count": c, "seconds": t * 1e-9,
                            "host_only_s": h * 1e-9}
                        for n, (c, t, h) in per_note.items()},
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, (_, t) in top_ops[:TOP]],
            "idle_gaps": [[n, (e - s) * 1e-9] for n, s, e in longest
                          if n != WAIT][:TOP],
        },
    }


def module_time(reduced: dict, token: str) -> tuple:
    """(count, seconds) summed over programs whose name holds `token`."""
    count, secs = 0, 0.0
    for name, v in reduced["modules"].items():
        if token in name:
            count += v["count"]
            secs += v["seconds"]
    return count, secs


def op_time(reduced: dict, prefix: str) -> tuple:
    """(count, seconds) summed over device ops whose name starts with
    `prefix`."""
    count, secs = 0, 0.0
    for name, v in reduced["ops"].items():
        if name.startswith(prefix):
            count += v["count"]
            secs += v["seconds"]
    return count, secs
