#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are data:
``BENCHMARK.json`` at the checkout's root names them, the configuration
is ``bench/configs/<config>.json``, the traffic ``bench/traffic/<traffic>.json``
(whose ``driver`` names ``bench/drivers/<driver>.py``), and each per-layer
metric a reader ``bench/metrics/<metric>.py``.  The run loads, warms up
every shape the cell's traffic uses (set-up), measures for ``--seconds``,
checks what the timed path produced against the reference, and prints one
JSON object as the last line of standard output.  With ``--trace 1`` the
per-layer metrics come from a profiler trace of a stretch of the window;
with ``--trace 0`` the end-to-end metrics.

Without a TPU, with fewer chips than the cell asks for, or on a device
the peak table does not know, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """Everything one cell runs from, found by the names in BENCHMARK.json."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "root": str(root)}


class CompileMonitor:
    """Counts JAX's backend compiles (and their seconds) and persistent
    compilation-cache hits, from ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def line(self) -> str:
        return (f"compile_s={self.seconds:.3f} programs={self.compiles} "
                f"persistent_cache_hits={self.cache_hits}")


def check_device(chips: int) -> tuple:
    """(devices, peaks) or SystemExit: the benchmark runs on a TPU the
    peak table knows, with at least `chips` chips, or not at all."""
    import jax

    from bench.work import peaks_for
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU attached (platform {dev.platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, found {len(devices)}")
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        raise SystemExit(str(e)) from e
    return devices, peaks


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float,
             monitor: CompileMonitor) -> dict:
    """Drive one run of the cell and assemble its result object."""
    driver = load_module(BENCH / "drivers" / f"{spec['traffic']['driver']}.py")
    rec = driver.drive(spec, seed, seconds, trace, t_start=t_start,
                       peaks=peaks, monitor=monitor, log=log)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(rec)
            if value is None:
                log(f"metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        for note in rec.get("notes", []):
            log(note)
    else:
        for name, m in e2e.items():
            if name not in rec["end_to_end"]:
                raise KeyError(f"the {spec['traffic']['driver']} driver "
                               f"does not report {name}")
            metrics[name] = {"value": rec["end_to_end"][name],
                             "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    if trace:
        device["busy_s"] = rec["reduced"]["busy_s"]
        device["window_s"] = rec["reduced"]["window_s"]
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = rec["reduced"]["breakdown"]
    out["checked"] = rec["checked"]
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        spec = load_spec(a.workload)
        from repro.launch.compile_cache import enable_compile_cache
    except (OSError, KeyError, ImportError) as e:
        print(f"bench: cannot load the cell or the program: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    # every program, however quick to compile, comes from the cache in a
    # run after the first, so set-up repeats
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    monitor = CompileMonitor()
    try:
        devices, peaks = check_device(int(spec["cell"]["chips"]))
    except SystemExit as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    log(f"workload={a.workload} seed={a.seed} seconds={a.seconds} "
        f"trace={a.trace} compile_cache={cache_dir}")
    try:
        out = run_cell(spec, a.seed, a.seconds, bool(a.trace), devices,
                       peaks, T_PROCESS, monitor)
    except Exception:  # noqa: BLE001 — any failure fails the run
        traceback.print_exc()
        print("bench: FAILED", file=sys.stderr)
        return 1
    log(monitor.line())
    for name, v in out["checked"].items():
        print(f"checked {name}={v['value']} limit={v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
