"""The reduction from trace events to busy, idle and per-program times:
exact on a hand-made trace, and consistent on a trace recorded on a
TPU v5e (``data/``)."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import readers, trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, end):
    return (plane, line, name, float(start), float(end - start))


def test_hand_made_trace():
    events = [
        ev(DEV, tr.OPS_LINE, "fusion.1", 0, 10),
        ev(DEV, tr.OPS_LINE, "fusion.2", 5, 15),
        ev(DEV, tr.OPS_LINE, "paged_attention.3", 22, 30),
        ev(DEV, tr.MODULES_LINE, "jit_decode_step(1)", 0, 15),
        ev(DEV, tr.MODULES_LINE, "jit_chunk_step(2)", 22, 30),
        ev(HOST, "python3", "bench.tick", 0, 18),
        ev(HOST, "python3", "bench.decode", 1, 3),
        ev(HOST, "python3", "bench.wait_arrival", 18, 21),
        ev(HOST, "python3", "bench.tick", 21, 40),
    ]
    r = tr.reduce_events(events, window=(0.0, 40.0))
    assert r["busy_s"] == pytest.approx(23e-9)
    # gaps: [15, 18) under the tick, [18, 21) waiting for an arrival
    # (not idle), [21, 22) and [30, 40) under the second tick
    assert r["wait_s"] == pytest.approx(3e-9)
    assert r["idle_s"] == pytest.approx(14e-9)
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["modules"]["jit_decode_step(1)"] == {
        "count": 1, "seconds": pytest.approx(15e-9)}
    tick = r["annotations"]["bench.tick"]
    assert tick["count"] == 2
    assert tick["host_only_s"] == pytest.approx((3 + 1 + 10) * 1e-9)
    assert r["breakdown"]["idle_gaps"][0] == ["bench.tick",
                                              pytest.approx(10e-9)]
    assert all(n != tr.WAIT for n, _ in r["breakdown"]["idle_gaps"])
    assert r["breakdown"]["device_ops"][0][0] == "fusion.1"
    assert tr.op_time(r, "paged_attention") == (1, pytest.approx(8e-9))
    assert tr.op_name("%fusion.1 = bf16[2] fusion(%paged_attention.3)") \
        == "fusion.1"
    rec = {"reduced": r, "decode_live": [], "model": {}, "peaks": {}}
    assert readers.host_ms_per_tick(rec) == pytest.approx(7e-6)
    assert readers.idle_share(rec) == pytest.approx(14 / 37 * 100)
    assert readers.decode_step_ms(rec) == pytest.approx(15e-6)
    # nothing to read: no decode call was recorded
    assert readers.decode_mfu(rec) is None
    assert readers.paged_attn_roofline(rec) is None


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events([ev(HOST, "python3", "bench.tick", 0, 5)])


def sampled_busy(ops, w0, w1, n):
    """Busy time by sampling the timeline at n points (an independent
    count: the latest end among ops started by t covers t or nothing
    does)."""
    starts = np.array([s for s, _ in ops])
    ends = np.maximum.accumulate(np.array([s + d for s, d in ops]))
    t = w0 + (np.arange(n) + 0.5) * (w1 - w0) / n
    i = np.searchsorted(starts, t, side="right") - 1
    hit = (i >= 0) & (ends[np.maximum(i, 0)] > t)
    return hit.sum() * (w1 - w0) / n


@pytest.mark.parametrize("path", sorted(DATA.glob("*.json.gz")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    events = tr.read_events(str(path))
    r = tr.reduce_events(events)
    assert r["device"].startswith("/device:TPU")
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] + r["idle_s"] + r["wait_s"] == \
        pytest.approx(r["window_s"], rel=1e-9)
    ops = sorted((s, d) for p, ln, _, s, d in events
                 if p == r["device"] and ln == tr.OPS_LINE)
    notes = [(s, s + d) for p, _, n, s, d in events if n.startswith("bench.")]
    w0, w1 = min(s for s, _ in notes), max(e for _, e in notes)
    assert r["busy_s"] * 1e9 == pytest.approx(
        sampled_busy(ops, w0, w1, 200_000), rel=0.01)
    n_decode, _ = tr.module_time(r, readers.DECODE)
    host_decode = r["annotations"]["bench.decode"]["count"]
    assert abs(n_decode - host_decode) <= 2
    for v in r["annotations"].values():
        assert 0 <= v["host_only_s"] <= v["seconds"] + 1e-12
    assert len(r["breakdown"]["device_ops"]) <= tr.TOP
    assert len(r["breakdown"]["idle_gaps"]) <= tr.TOP
