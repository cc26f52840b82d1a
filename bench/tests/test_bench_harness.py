"""The harness end to end at smoke size on the CPU, past its look for a
chip: sound runs come out correct, and runs with the timed path broken
underneath come out not correct.  Also the contract's checks on
BENCHMARK.json and on runs without a chip or without the program."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import smoke
from bench.work import peaks_for

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242
E2E = [{"name": n, "unit": u} for n, u in (
    ("setup_s", "s"), ("output_tokens_per_s", "tokens/s"))]
OPEN_E2E = E2E + [{"name": "ttft_p90_ms", "unit": "ms"},
                  {"name": "itl_p99_ms", "unit": "ms"}]


@pytest.fixture(scope="module")
def monitor():
    return run.CompileMonitor()


def go(config, traffic, e2e, monitor, tmp_path):
    sp = smoke.spec(config, traffic, str(tmp_path))
    sp["end_to_end"] = e2e
    return run.run_cell(sp, SEED, 1.5, False, jax.devices(),
                        peaks_for("TPU v5 lite"), time.perf_counter(),
                        monitor)


def _token_altered(logits, cache):
    # every produced token altered where it is produced: the logits the
    # pick reads are rolled along the vocabulary
    return jnp.roll(logits, 1, axis=-1), cache


def _kv_dropped(logits, cache):
    # the step loses the KV state it should have kept
    return logits, dict(cache, k=jnp.zeros_like(cache["k"]),
                        v=jnp.zeros_like(cache["v"]))


FAULTS = {"token": _token_altered, "kv_dropped": _kv_dropped}


def broken_decode(fault):
    """The program's decode step, broken underneath the harness."""
    from repro.serving.engine import ServeEngine
    sound = ServeEngine.decode_fn

    def decode_fn(self, *args):
        return FAULTS[fault](*sound(self, *args))

    return mock.patch.object(ServeEngine, "decode_fn", decode_fn)


@pytest.mark.parametrize("config,traffic,e2e", [
    (smoke.DEEPSEEK, smoke.OPEN, OPEN_E2E),
    (smoke.STABLELM2, smoke.CLOSED, E2E),
], ids=["deepseek-open", "stablelm2-closed"])
def test_sound_run_is_correct(config, traffic, e2e, monitor, tmp_path):
    out = go(config, traffic, e2e, monitor, tmp_path)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in e2e}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checked"
    chk = out["checked"]
    for name in traffic["check"]["limits"]:
        assert chk[name]["value"] <= chk[name]["limit"]
    assert chk["programs_in_window"] == {"value": 0, "limit": 0}
    assert chk["served_tokens_compared"]["value"] >= \
        chk["served_tokens_compared"]["limit"]


@pytest.mark.parametrize("fault", ["token", "kv_dropped"])
def test_broken_timed_path_is_not_correct(fault, monitor, tmp_path):
    with broken_decode(fault):
        out = go(smoke.STABLELM2, smoke.OPEN, OPEN_E2E, monitor, tmp_path)
    assert out["correct"] is False
    chk = out["checked"]
    assert all(chk[name]["value"] > chk[name]["limit"]
               for name in smoke.OPEN["check"]["limits"])


@pytest.mark.parametrize("config", [smoke.DEEPSEEK, smoke.STABLELM2],
                         ids=["deepseek", "stablelm2"])
def test_float8_control_fails_where_the_program_passes(config, monitor,
                                                       tmp_path):
    """The control of the check: the reference in float8 put in the
    program's place on the same served requests and positions, judged by
    the same readings against the same limits."""
    traffic = dict(smoke.CLOSED, output={"dist": "uniform", "min": 40,
                                         "max": 60},
                   check=dict(smoke.CLOSED["check"], tokens=150))
    sp = smoke.spec(config, traffic, str(tmp_path))
    driver = run.load_module(run.BENCH / "drivers" / "serve.py")
    rec = driver.drive(sp, SEED, 1.5, False, t_start=time.perf_counter(),
                       peaks=peaks_for("TPU v5 lite"), monitor=monitor,
                       log=lambda *_: None)
    assert rec["checked"]["served_tokens_compared"]["value"] >= 150
    assert rec["correct"]
    ctl = rec["control"]()
    assert ctl["correct"] is False
    assert set(ctl["checked"]) == set(traffic["check"]["limits"])
    assert ctl["readings"]["logit_err"] > \
        traffic["check"]["limits"]["logit_err"]


def test_benchmark_json_is_consistent():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cell in bench["workloads"]:
        sp = run.load_spec(cell["name"], ROOT)
        assert (ROOT / "bench" / "drivers"
                / f"{sp['traffic']['driver']}.py").exists()
        names = {m["name"] for m in sp["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        layer = [m for m in sp["per_layer"]]
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in names, (cell["name"], m["name"])
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        run.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py").read
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepseek-7b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    res = _bench_cmd(ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not res.stdout.strip()


def test_benchmark_alone_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _bench_cmd(tmp_path)
    assert res.returncode != 0
    assert not res.stdout.strip()
