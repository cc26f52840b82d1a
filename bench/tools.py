#!/usr/bin/env python3
"""The benchmark's own tools, each one process on the chip.

    python3 bench/tools.py sweep --workload W --rates 1,2,3 --seconds S --seed N
        Runs the open-loop cell once per arrival rate and prints offered
        and completed rates, the backlog at the close and the tails: the
        sweep that finds the knee a cell's rate is set from.
    python3 bench/tools.py control --workload W --seeds a,b,c --seconds S
        Runs the cell once per seed and prints, on the same sampled
        requests, the program's readings and the float8 control's, each
        judged against the cell's limits: the readings a limit is set from.
    python3 bench/tools.py events --workload W --seed N --seconds S --out F
        A traced run whose trace events are saved to F (gzip JSON), for
        the trace reduction's test.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("tool", choices=("sweep", "control", "events"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seeds", default="")
    p.add_argument("--rates", default="")
    p.add_argument("--out", default="")
    a = p.parse_args(argv)
    spec = run.load_spec(a.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    monitor = run.CompileMonitor()
    devices, peaks = run.check_device(int(spec["cell"]["chips"]))
    driver = run.load_module(run.BENCH / "drivers"
                             / f"{spec['traffic']['driver']}.py")

    def once(sp, seed, trace=False):
        return driver.drive(sp, seed, a.seconds, trace,
                            t_start=time.perf_counter(), peaks=peaks,
                            monitor=monitor, log=run.log)

    if a.tool == "sweep":
        for rate in [float(r) for r in a.rates.split(",")]:
            sp = copy.deepcopy(spec)
            sp["traffic"]["arrivals"]["rate_per_s"] = rate
            rec = once(sp, a.seed)
            print(json.dumps({"rate_per_s": rate, **rec["end_to_end"],
                              "correct": rec["correct"],
                              "readings": rec["readings"]}),
                  flush=True)
            del rec
    elif a.tool == "control":
        for seed in [int(s) for s in a.seeds.split(",")]:
            rec = once(spec, seed)
            ctl = rec["control"]()
            print(json.dumps({"seed": seed, "program": rec["readings"],
                              "program_correct": rec["correct"],
                              "control": ctl["readings"],
                              "control_correct": ctl["correct"],
                              "checked": rec["checked"]}), flush=True)
            del rec, ctl
    else:
        rec = once(spec, a.seed, trace=True)
        from trace_reduce import save_events
        save_events(rec["events"], a.out)
        print(json.dumps({"reduced": {k: v for k, v in rec["reduced"].items()
                                      if k != "ops"},
                          "decode_calls": len(rec["decode_live"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
