#!/usr/bin/env python3
"""A traced run of one cell, attributed to the program's own spans and
scopes.

    python3 bench/attribute.py --workload W --seed N --seconds S
        [--out F --keep-ms M]

Runs the cell as ``bench/run.py --trace 1`` does, but reads the trace with
``bench/program_trace.py``: the idle gaps are labelled by the innermost
``serve.*`` span as well as by the ``bench.*`` annotations, and each op
of the decode step by its ``jax.named_scope`` path.  Prints the cell's
per-layer metrics, the program's (``step_host_ms``, ``decode_kv_write_ms``),
the per-phase table (host time, device-idle time and the idle time under
no child span, per span name) and the per-scope table (device ms per
decode step, each op's scope), then one JSON line.  The compile cache
is left off and XLA dumps the decode step's optimized HLO, where the
scopes are read (a TPU trace's op events do not carry them).  ``--out``
saves ``--keep-ms`` of the traced stretch's events (gzip JSON), for the
tests of ``program_trace``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def ticks(events: list, seconds: float) -> list:
    """A shorter recording: the events that start from the first
    scheduler tick (``bench.tick``) to the end of the last tick that
    starts within `seconds` of it."""
    starts = sorted((e[3], e[3] + e[4]) for e in events
                    if e[2] == "bench.tick")
    t0 = starts[0][0]
    t1 = max(e for s, e in starts if s < t0 + seconds * 1e9)
    return [e for e in events if t0 <= e[3] < t1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", default="")
    p.add_argument("--keep-ms", type=float, default=300.0)
    a = p.parse_args(argv)
    spec = run.load_spec(a.workload)
    hlo_dir = os.path.join(spec["root"], ".bench_out", "hlo",
                           f"{a.workload}-{a.seed}")
    shutil.rmtree(hlo_dir, ignore_errors=True)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={hlo_dir}",
        "--xla_dump_hlo_as_text", "--xla_dump_hlo_module_re=decode_step")))
    import jax
    # every program compiles here, so XLA dumps the decode step's HLO
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import program_trace as pt, trace_reduce as tr
    monitor = run.CompileMonitor()
    devices, peaks = run.check_device(int(spec["cell"]["chips"]))
    driver = run.load_module(run.BENCH / "drivers"
                             / f"{spec['traffic']['driver']}.py")
    loaded = []

    def load_program_events(trace_dir):
        loaded.extend(pt.load_events(trace_dir, pt.hlo_dump(hlo_dir)))
        return pt.plain(loaded)

    with mock.patch.object(driver, "load_events", load_program_events):
        rec = driver.drive(spec, a.seed, a.seconds, True,
                           t_start=time.perf_counter(), peaks=peaks,
                           monitor=monitor, log=run.log)
    rec["scopes"] = pt.scopes(loaded)
    metrics = {}
    for m in spec["per_layer"]:
        reader = run.load_module(run.BENCH / "metrics" / f"{m['name']}.py")
        metrics[m["name"]] = reader.read(rec)
    metrics["step_host_ms"] = pt.step_host_ms(rec)
    metrics["decode_kv_write_ms"] = pt.decode_kv_write_ms(rec)
    for name, v in metrics.items():
        run.log(f"metric {name}: {v}")
    phase = pt.phases(loaded)
    run.log("phase: count host_ms idle_ms self_idle_ms (sums over the "
            "traced stretch)")
    for name, v in sorted(phase.items(), key=lambda kv: -kv[1]["idle_s"]):
        run.log(f"phase {name}: {v['count']} {v['seconds'] * 1e3:.3f} "
                f"{v['idle_s'] * 1e3:.3f} {v['self_idle_s'] * 1e3:.3f}")
    sc = rec["scopes"]
    if sc:
        run.log(f"scopes of {sc['module']}: calls={sc['calls']} "
                f"ms_per_call={sc['seconds_per_call'] * 1e3:.3f}")
        for path, t in sc["scopes"].items():
            run.log(f"scope {path}: {t * 1e3:.4f} ms per call")
        for op, v in list(sc["ops"].items())[:40]:
            run.log(f"scope op {op}: {v['scope']} "
                    f"{v['seconds'] * 1e3:.4f} ms per call")
    if a.out:
        tr.save_events(ticks(loaded, a.keep_ms * 1e-3), a.out)
    print(json.dumps({
        "workload": a.workload, "seed": a.seed, "correct": rec["correct"],
        "metrics": metrics, "phases": phase, "scopes": sc,
        "breakdown": rec["reduced"]["breakdown"],
        "window_s": rec["reduced"]["window_s"],
        "busy_s": rec["reduced"]["busy_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
