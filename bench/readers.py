"""Per-layer readers over a traced run's record.

Each metric file under ``bench/metrics/`` names one of these.  A reader
returns ``None`` when the trace holds nothing it can read (no decode step
in the traced stretch, say), and the harness then leaves the metric out.

The record holds ``reduced`` (``trace_reduce.reduce_events``),
``decode_live`` (the live KV length of each active row of every decode
call made while the trace ran), ``model`` (``reference.model_dims``) and
``peaks`` (``work.peaks_for``).  Program names the trace shows: the
jitted steps are ``jit_decode_step`` and ``jit_chunk_step``; the paged
decode kernel's ops are named ``paged_attention.<n>``.
"""

from __future__ import annotations

import numpy as np

from bench import work
from bench.trace_reduce import module_time, op_time

DECODE = "jit_decode_step"
CHUNK = "jit_chunk_step"
KERNEL = "paged_attention"


def _per_call_ms(rec, token):
    n, secs = module_time(rec["reduced"], token)
    return secs / n * 1e3 if n else None


def host_ms_per_tick(rec):
    """Mean host time per scheduler tick with no op on the device."""
    tick = rec["reduced"]["annotations"].get("bench.tick")
    if not tick or not tick["count"]:
        return None
    return tick["host_only_s"] / tick["count"] * 1e3


def prefill_chunk_ms(rec):
    return _per_call_ms(rec, CHUNK)


def decode_step_ms(rec):
    return _per_call_ms(rec, DECODE)


def decode_mfu(rec):
    """The decode step's required time at the chip's peaks (the larger of
    its FLOPs and its bytes), over its measured device time, in %."""
    n, secs = module_time(rec["reduced"], DECODE)
    live = [x for x in rec["decode_live"] if x]
    if not n or not live:
        return None
    need = [work.roofline_s(*work.decode_step(rec["model"], x), rec["peaks"])
            for x in live]
    memory = sum(1 for _, b in need if b == "memory")
    rec.setdefault("notes", []).append(
        f"decode_mfu: {memory} of {len(need)} decode steps bound by "
        f"memory, the rest by compute")
    return float(np.mean([t for t, _ in need])) / (secs / n) * 100.0


def paged_attn_roofline(rec):
    """The paged-attention kernel's required time per call (q, the live K
    and V once, the output) over its measured device time per call, in %."""
    n, secs = op_time(rec["reduced"], KERNEL)
    live = [x for x in rec["decode_live"] if x]
    if not n or not live:
        return None
    need = [work.roofline_s(*work.paged_attention_call(rec["model"], x),
                            rec["peaks"])[0] for x in live]
    return float(np.mean(need)) / (secs / n) * 100.0


def idle_share(rec):
    """Share of the traced stretch, arrival waits left out, in which no
    op ran on the device, in %."""
    r = rec["reduced"]
    working = r["window_s"] - r["wait_s"]
    if working <= 0:
        return None
    return r["idle_s"] / working * 100.0
