"""Structural validator for ``BENCH_serving.json``.

The serving benchmark table is a regression *baseline*: downstream
gates diff it cell-by-cell, so its shape has to be stable — known cell
names, known metric keys per cell, and the NaN→null convention (the
file is strict JSON; non-finite floats are written as ``null``, never
as the ``NaN`` / ``Infinity`` literals Python's ``json`` would happily
emit and almost nothing else can parse).

This module checks exactly that, with no repo imports, so CI can run
it *before* the (much slower) smoke benchmark and fail fast when a PR
adds a cell or key without updating the schema here — the same
add-a-cell-refresh-the-baseline discipline ``serving_throughput.py``
enforces at run time, applied statically to the checked-in file.

Usage::

    python benchmarks/validate_bench.py [BENCH_serving.json]

Exit status 0 and silence on success; a numbered list of problems and
exit status 1 otherwise.  ``check(data)`` returns the problem list for
use from tests.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

# Top-level keys of the bench file.  "cells" holds the table proper.
TOP_KEYS = {"arch", "cells", "max_len", "n_requests", "target",
            "trace_seed"}

# Metric-key sets shared by several cells.
_SINGLE = {"decode_steps", "generated_tokens",
           "hbm_bytes_per_admitted_token", "mean_ttft_steps",
           "occupancy", "peak_active", "pool_bytes", "preemptions",
           "slots", "tokens_per_s", "tokens_per_step"}
_SPEC = {"accepted_per_verify", "arch", "decode_steps",
         "generated_tokens", "spec_accepted_tokens",
         "spec_drafted_tokens", "spec_k", "spec_verify_steps",
         "tokens_per_s", "tokens_per_step"}
_LONGPROMPT = {"decode_steps", "generated_tokens", "mean_ttft_steps",
               "overlap_steps", "prefill_chunk", "prefill_chunks",
               "prefill_compiles", "prefill_queue_peak", "replicas",
               "reroutes", "tokens_per_s", "tokens_per_step"}
_SHAREDPREFIX = {"decode_steps", "generated_tokens", "load_imbalance",
                 "mean_ttft_steps", "prefill_tokens",
                 "prefill_tokens_saved", "prefix_hit_rate",
                 "prefix_hits", "prefix_misses", "replicas",
                 "route_policy", "tokens_per_s", "tokens_per_step"}
_OPENLOOP = {"arrival_gap", "arrival_seed", "arrivals",
             "autoscale_drains", "autoscale_grows", "generated_tokens",
             "goodput_tokens", "p50_e2e_steps", "p50_ttft_steps",
             "p99_e2e_steps", "p99_ttft_steps", "peak_replicas",
             "replicas", "slo_e2e_steps", "slo_ttft_steps",
             "tokens_per_s", "total_vsteps"}

# The full cell schema: every cell the smoke bench emits, with its
# exact key set.  Adding a bench cell means adding a row here — the
# validator (and the CI step running it) fails otherwise.
CELL_SCHEMA = {
    "paged_static": _SINGLE,
    "paged_continuous": _SINGLE,
    "paged_continuous_kernel": _SINGLE | {"kv_kernel"},
    "paged_spec_off": _SPEC,
    "paged_spec_on": _SPEC,
    "router_least_loaded_x3": {
        "decode_steps", "generated_tokens", "in_flight_vs_single",
        "load_imbalance", "peak_in_flight", "replicas", "reroutes",
        "route_policy", "tokens_per_s", "tokens_per_step"},
    "longprompt_router_blocking": _LONGPROMPT,
    "longprompt_router_chunked": _LONGPROMPT,
    "sharedprefix_router_cold": _SHAREDPREFIX,
    "sharedprefix_router_cached": _SHAREDPREFIX,
    "openloop_poisson_fixed": _OPENLOOP,
    "openloop_poisson_autoscale": _OPENLOOP,
    "telemetry_overhead": {
        "decode_steps", "generated_tokens", "mean_ttft_steps",
        "ring_events", "tokens_per_s", "tokens_per_step",
        "trace_spans"},
}

# Keys whose values are strings, not numbers.
_STR_KEYS = {"arch", "arrivals", "kv_kernel", "route_policy"}

# Cells are gated positions: downstream regression gates diff every
# cell key against the baseline, so a wall-clock-derived key here would
# gate on machine noise.  `tokens_per_s` is the one advisory wall
# metric the table carries (the run-time gate strips it); anything
# spelled `wall_*` is rejected outright.
_WALL_PREFIX = "wall_"


def _reject_constant(name: str) -> float:
    raise ValueError(f"non-finite JSON literal {name!r} — the bench "
                     f"writes NaN as null")


def parse_strict(text: str):
    """``json.loads`` that rejects NaN / Infinity literals."""
    return json.loads(text, parse_constant=_reject_constant)


def check(data) -> list[str]:
    """Return a list of structural problems (empty when valid)."""
    problems: list[str] = []
    if not isinstance(data, dict):
        return [f"top level is {type(data).__name__}, expected object"]

    missing = TOP_KEYS - data.keys()
    extra = data.keys() - TOP_KEYS
    if missing:
        problems.append(f"missing top-level keys: {sorted(missing)}")
    if extra:
        problems.append(f"unknown top-level keys: {sorted(extra)}")

    cells = data.get("cells")
    if not isinstance(cells, dict):
        problems.append("'cells' is not an object")
        return problems

    missing_cells = CELL_SCHEMA.keys() - cells.keys()
    extra_cells = cells.keys() - CELL_SCHEMA.keys()
    if missing_cells:
        problems.append(f"missing cells: {sorted(missing_cells)}")
    if extra_cells:
        problems.append(f"unknown cells: {sorted(extra_cells)} — "
                        f"register new cells in CELL_SCHEMA")

    for name in sorted(CELL_SCHEMA.keys() & cells.keys()):
        cell, want = cells[name], CELL_SCHEMA[name]
        if not isinstance(cell, dict):
            problems.append(f"cell {name!r} is not an object")
            continue
        if missing := want - cell.keys():
            problems.append(f"cell {name!r} missing keys: "
                            f"{sorted(missing)}")
        if extra := cell.keys() - want:
            problems.append(f"cell {name!r} unknown keys: "
                            f"{sorted(extra)}")
        if wall := sorted(k for k in (cell.keys() | want)
                          if k.startswith(_WALL_PREFIX)):
            problems.append(
                f"cell {name!r} carries wall-clock keys {wall} in a "
                f"gated position — gated metrics must be vstep-derived"
                f" (tokens_per_s is the only advisory wall metric)")
        for key in sorted(want & cell.keys()):
            val = cell[key]
            if key in _STR_KEYS:
                if not isinstance(val, str):
                    problems.append(f"{name}.{key} should be a string, "
                                    f"got {val!r}")
            elif not (val is None or isinstance(val, (int, float))):
                problems.append(f"{name}.{key} should be numeric or "
                                f"null, got {val!r}")
            elif isinstance(val, float) and val != val:
                problems.append(f"{name}.{key} is NaN — write null")
    return problems


def validate_file(path) -> list[str]:
    """Parse *path* strictly and return its problem list."""
    try:
        text = Path(path).read_text()
    except OSError as e:
        return [f"cannot read {path}: {e}"]
    try:
        data = parse_strict(text)
    except ValueError as e:
        return [f"{path} is not strict JSON: {e}"]
    return check(data)


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else list(argv)
    path = args[0] if args else "BENCH_serving.json"
    problems = validate_file(path)
    if problems:
        print(f"{path}: {len(problems)} problem(s)")
        for i, p in enumerate(problems, 1):
            print(f"  {i}. {p}")
        return 1
    print(f"{path}: OK ({len(CELL_SCHEMA)} cells)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
