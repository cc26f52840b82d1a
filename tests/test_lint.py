"""easeylint: per-rule fixtures, suppression, CLI schema, repo-clean.

Every rule gets a violating snippet and a passing twin — the twin is as
important as the violation: a rule that fires on the repo idiom would
train everyone to sprinkle pragmas.  The repo-clean test then pins the
real invariant: ``src/`` + ``benchmarks/`` lint with zero errors under
the bundled allowlist.
"""

import json
import sys
from pathlib import Path

import pytest

from repro.analysis.lint import (LintConfig, default_config, lint_paths,
                                 lint_source)
from repro.analysis.lint import toml_lite
from repro.analysis.lint.__main__ import JSON_VERSION, main as lint_main

REPO = Path(__file__).parent.parent
sys.path.insert(0, str(REPO / "benchmarks"))

import validate_bench  # noqa: E402


def _errors(text, rel, cfg=None, rules=None):
    return [f for f in lint_source(text, rel, cfg, rules)
            if f.severity == "error"]


def _rules_fired(text, rel, cfg=None, rules=None):
    return {f.rule for f in _errors(text, rel, cfg, rules)}


# ---------------------------------------------------------------------------
# rule: wall-clock

def test_wall_clock_fires_on_call_and_reference():
    bad = (
        "import time\n"
        "def f():\n"
        "    return time.time()\n"
        "def g(clock=time.perf_counter):\n"
        "    return clock()\n"
    )
    errs = _errors(bad, "src/repro/x.py", rules=["wall-clock"])
    assert len(errs) == 2
    assert {e.line for e in errs} == {3, 4}


def test_wall_clock_catches_bare_import_and_datetime():
    bad = (
        "from time import perf_counter as pc\n"
        "import datetime\n"
        "def f():\n"
        "    return pc(), datetime.datetime.now()\n"
    )
    assert len(_errors(bad, "src/repro/x.py", rules=["wall-clock"])) == 2


def test_wall_clock_passing_twin_injected_clock():
    good = (
        "def f(clock):\n"
        "    return clock()\n"
        "def g(now=None):\n"
        "    return 0.0 if now is None else now\n"
    )
    assert _errors(good, "src/repro/x.py", rules=["wall-clock"]) == []


def test_wall_clock_exempts_timed_helper():
    good = (
        "import time\n"
        "def _timed(fn):\n"
        "    t0 = time.perf_counter()\n"
        "    out = fn()\n"
        "    return out, time.perf_counter() - t0\n"
    )
    assert _errors(good, "src/repro/x.py", rules=["wall-clock"]) == []


def test_wall_clock_pragma_same_line_and_line_above():
    good = (
        "import time\n"
        "a = time.time()  # easeylint: allow[wall-clock] — advisory\n"
        "# easeylint: allow[wall-clock]\n"
        "b = time.time()\n"
    )
    assert _errors(good, "src/repro/x.py", rules=["wall-clock"]) == []


def test_allowlist_suppresses_by_path_and_requires_reason():
    cfg = LintConfig.from_text(
        '[[allow]]\nrule = "wall-clock"\npath = "src/repro/adv/"\n'
        'reason = "wall-clock FOM file"\n')
    bad = "import time\nt = time.time()\n"
    assert _errors(bad, "src/repro/adv/b.py", cfg, ["wall-clock"]) == []
    assert len(_errors(bad, "src/repro/core/b.py", cfg,
                       ["wall-clock"])) == 1
    with pytest.raises(ValueError, match="reason"):
        LintConfig.from_text(
            '[[allow]]\nrule = "wall-clock"\npath = "x.py"\n')


# ---------------------------------------------------------------------------
# rule: telemetry-guard

def test_telemetry_guard_fires_unguarded():
    bad = (
        "def step(self):\n"
        "    self.tracer.begin('decode', 0)\n"
    )
    errs = _errors(bad, "src/repro/serving/x.py",
                   rules=["telemetry-guard"])
    assert len(errs) == 1 and errs[0].line == 2


def test_telemetry_guard_passing_idioms():
    good = (
        "def step(self, tracer):\n"
        "    if self.tracer is not None:\n"
        "        self.tracer.begin('a', 0)\n"
        "    if tracer is None:\n"
        "        return\n"
        "    tracer.emit('b')\n"
        "    ok = tracer is not None and tracer.emit('c')\n"
        "def other(sink):\n"
        "    assert sink is not None\n"
        "    sink.emit('d')\n"
    )
    assert _errors(good, "src/repro/serving/x.py",
                   rules=["telemetry-guard"]) == []


def test_telemetry_guard_nested_def_does_not_inherit():
    bad = (
        "def outer(tracer):\n"
        "    if tracer is not None:\n"
        "        def cb():\n"
        "            tracer.begin('x', 0)\n"  # closure may outlive guard
        "        return cb\n"
    )
    assert len(_errors(bad, "src/repro/serving/x.py",
                       rules=["telemetry-guard"])) == 1


# ---------------------------------------------------------------------------
# rule: keyed-rng

def test_keyed_rng_scoped_to_serving():
    bad = "import jax\nk = jax.random.PRNGKey(0)\n"
    assert _rules_fired(bad, "src/repro/serving/x.py",
                        rules=["keyed-rng"]) == {"keyed-rng"}
    assert _errors(bad, "src/repro/training/x.py",
                   rules=["keyed-rng"]) == []


def test_keyed_rng_fires_on_unfolded_and_reused_keys():
    bad = (
        "import jax\n"
        "def sample(base, logits):\n"
        "    k = jax.random.PRNGKey(7)\n"
        "    a = jax.random.categorical(k, logits)\n"       # base key draw
        "    b = jax.random.uniform(base)\n"
        "    c = jax.random.uniform(base)\n"                # reuse of param
        "    return a, b, c\n"
    )
    errs = _errors(bad, "src/repro/serving/x.py", rules=["keyed-rng"])
    msgs = "\n".join(e.message for e in errs)
    assert "literal PRNGKey(7)" in msgs
    assert "base key `k`" in msgs
    assert "reused" in msgs


def test_keyed_rng_passing_fold_in_chain():
    good = (
        "import jax\n"
        "def sample(base, rid, step, logits):\n"
        "    k = jax.random.fold_in(jax.random.fold_in(base, rid), step)\n"
        "    return jax.random.categorical(k, logits)\n"
    )
    assert _errors(good, "src/repro/serving/x.py",
                   rules=["keyed-rng"]) == []


# ---------------------------------------------------------------------------
# rule: jit-purity

def test_jit_purity_fires_on_captured_mutation_and_tracer():
    bad = (
        "import jax\n"
        "log = []\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    log.append(x)\n"
        "    return x * 2\n"
    )
    errs = _errors(bad, "src/repro/training/x.py", rules=["jit-purity"])
    assert len(errs) == 1 and "log.append" in errs[0].message
    bad2 = (
        "import jax\n"
        "def step(x, tracer):\n"
        "    tracer.emit('inside-trace')\n"
        "    return x\n"
        "out = jax.jit(step)\n"
    )
    assert _rules_fired(bad2, "src/repro/training/x.py",
                        rules=["jit-purity"]) == {"jit-purity"}


def test_jit_purity_transitive_and_pallas_refs_ok():
    # helper called from the scanned fn is traced transitively...
    bad = (
        "import jax.lax as lax\n"
        "seen = set()\n"
        "def helper(c):\n"
        "    seen.add(c)\n"
        "    return c\n"
        "def body(c, x):\n"
        "    return helper(c), x\n"
        "out = lax.scan(body, 0, None)\n"
    )
    errs = _errors(bad, "src/repro/training/x.py", rules=["jit-purity"])
    assert len(errs) == 1 and "seen.add" in errs[0].message
    # ...while a pallas kernel writing its own o_ref parameter is pure
    good = (
        "from jax.experimental import pallas as pl\n"
        "def kernel(x_ref, o_ref):\n"
        "    acc = x_ref[...] * 2\n"
        "    o_ref[...] = acc\n"
        "def call(x):\n"
        "    return pl.pallas_call(kernel, out_shape=None)(x)\n"
    )
    assert _errors(good, "src/repro/kernels/x.py",
                   rules=["jit-purity"]) == []


# ---------------------------------------------------------------------------
# rule: refcount-pairing

def test_refcount_fires_on_leaked_acquisition():
    bad = (
        "def admit(pool, slot, pages):\n"
        "    pool.attach(slot, pages)\n"
        "    return True\n"
    )
    errs = _errors(bad, "src/repro/serving/x.py",
                   rules=["refcount-pairing"])
    assert len(errs) == 1 and "attach" in errs[0].message


def test_refcount_passing_release_escape_and_raise():
    good = (
        "def paired(pool, slot, pages):\n"
        "    pool.attach(slot, pages)\n"
        "    pool.free(slot)\n"
        "def handoff(pool, slot, pages):\n"
        "    pool.adopt_run(slot, pages)\n"
        "    return slot\n"                       # ownership moves out
        "def stored(self, pool, slot, pages):\n"
        "    pool.reserve_prefix(slot, pages)\n"
        "    self.slots[slot] = pages\n"          # escape via store
        "def failing(pool, slot, pages):\n"
        "    pool.attach(slot, pages)\n"
        "    raise RuntimeError('evicted')\n"     # exception path exempt
    )
    assert _errors(good, "src/repro/serving/x.py",
                   rules=["refcount-pairing"]) == []


def test_refcount_branch_must_release_on_both_paths():
    bad = (
        "def admit(pool, slot, pages, ok):\n"
        "    pool.attach(slot, pages)\n"
        "    if ok:\n"
        "        pool.free(slot)\n"
        "    return ok\n"                         # leak on the else path
    )
    assert len(_errors(bad, "src/repro/serving/x.py",
                       rules=["refcount-pairing"])) == 1


# ---------------------------------------------------------------------------
# rule: vmem-budget

_VMEM_CFG = LintConfig(vmem_bounds={"d": 256})


def _kernel_src(bx, by):
    return (
        "from jax.experimental import pallas as pl\n"
        "def kern(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def run(x):\n"
        f"    return pl.pallas_call(kern, grid=(1,),\n"
        f"        in_specs=[pl.BlockSpec(({bx}, {by}), lambda i: (i, 0))],\n"
        f"        out_specs=pl.BlockSpec(({bx}, {by}), lambda i: (i, 0)),\n"
        "        out_shape=None)(x)\n"
    )


def test_vmem_estimate_info_within_budget():
    out = lint_source(_kernel_src(128, "d"), "src/repro/kernels/x.py",
                      _VMEM_CFG, ["vmem-budget"])
    infos = [f for f in out if f.severity == "info"]
    assert len(infos) == 1 and "estimated VMEM" in infos[0].message
    assert [f for f in out if f.severity == "error"] == []


def test_vmem_inflated_blockspec_fails():
    # 8192*8192 f32 = 256 MiB per block, x2 specs x2 double-buffering
    errs = _errors(_kernel_src(8192, 8192), "src/repro/kernels/x.py",
                   _VMEM_CFG, ["vmem-budget"])
    assert len(errs) == 1 and "exceeds" in errs[0].message


def test_vmem_dynamic_dim_is_an_error():
    errs = _errors(_kernel_src("n_runtime", 128), "src/repro/kernels/x.py",
                   _VMEM_CFG, ["vmem-budget"])
    assert errs and "dynamic block dimension" in errs[0].message


def test_vmem_squeezed_dim_counts_one_element():
    """A ``None`` block dim (Pallas squeezes it) is one element: the
    block (None, 128, d) costs what (128, d) costs, and is no error."""
    def info(bx):
        out = lint_source(_kernel_src(bx, "d"), "src/repro/kernels/x.py",
                          _VMEM_CFG, ["vmem-budget"])
        assert [f for f in out if f.severity == "error"] == []
        return [f.message for f in out if f.severity == "info"]
    assert info("None, 128") == info(128)
    assert "2x256 KiB blocks" in info("None, 128")[0]


def test_vmem_scratch_and_bounds_resolution():
    src = (
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "import jax.numpy as jnp\n"
        "def kern(x_ref, o_ref, acc):\n"
        "    o_ref[...] = x_ref[...]\n"
        "def run(x, block_q: int = 64):\n"
        "    bq = min(block_q, 1 << 30)\n"
        "    return pl.pallas_call(kern, grid=(1,),\n"
        "        in_specs=[pl.BlockSpec((bq, d), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((bq, d), lambda i: (i, 0)),\n"
        "        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],\n"
        "        out_shape=None)(x)\n"
    )
    out = lint_source(src, "src/repro/kernels/x.py", _VMEM_CFG,
                      ["vmem-budget"])
    info = [f for f in out if f.severity == "info"][0]
    # blocks: 2 specs * 64*256*4 = 128 KiB (x2 buffering = 256), scratch 64
    assert "2x128 KiB blocks + 64 KiB scratch" in info.message
    assert [f for f in out if f.severity == "error"] == []


def test_vmem_reports_estimates_for_repo_kernels():
    cfg = default_config()
    want = {
        "src/repro/kernels/flash_attention.py": "flash_attention_pallas",
        "src/repro/kernels/paged_attention.py": "paged_attention_pallas",
        "src/repro/kernels/rmsnorm.py": "rmsnorm_pallas",
        "src/repro/kernels/sedov_stencil.py": "sedov_step_pallas",
    }
    for rel, fn_name in want.items():
        out = lint_source((REPO / rel).read_text(), rel, cfg,
                          ["vmem-budget"])
        infos = [f for f in out if f.severity == "info"]
        assert any(f"`{fn_name}`" in f.message for f in infos), rel


def test_vmem_budget_fraction_matches_tuning():
    from repro.analysis.lint.rules import vmem_budget
    from repro.core import tuning
    assert vmem_budget.VMEM_BUDGET_FRACTION == tuning.VMEM_BUDGET_FRACTION


# ---------------------------------------------------------------------------
# whole-repo invariants

def test_repo_lints_clean():
    findings, nfiles = lint_paths([REPO / "src", REPO / "benchmarks"])
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], "\n".join(f.render() for f in errors)
    assert nfiles > 50


def test_seeded_violation_fails_repo_lint():
    """An unguarded tracer call added to scheduler.py must fail CI."""
    rel = "src/repro/serving/scheduler.py"
    text = (REPO / rel).read_text()
    assert _errors(text, rel, default_config()) == []
    seeded = text + (
        "\n\ndef _drift(self):\n"
        "    self.tracer.begin('unguarded', 0)\n"
    )
    errs = _errors(seeded, rel, default_config())
    assert any(e.rule == "telemetry-guard" for e in errs)


# ---------------------------------------------------------------------------
# CLI / JSON schema

def test_cli_json_schema_stable(tmp_path, capsys):
    (tmp_path / "bad.py").write_text("import time\nt = time.time()\n")
    rc = lint_main([str(tmp_path / "bad.py"), "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert set(out) == {"version", "files", "rules", "errors", "infos",
                        "findings"}
    assert out["version"] == JSON_VERSION
    assert out["files"] == 1 and out["errors"] == 1
    assert set(out["findings"][0]) == {"rule", "path", "line", "col",
                                       "severity", "message", "hint"}


def test_cli_exit_codes(tmp_path, capsys):
    (tmp_path / "ok.py").write_text("x = 1\n")
    assert lint_main([str(tmp_path / "ok.py")]) == 0
    assert lint_main([str(tmp_path / "missing_dir")]) == 2
    capsys.readouterr()


def test_cli_unknown_rule_rejected(tmp_path):
    (tmp_path / "ok.py").write_text("x = 1\n")
    with pytest.raises(ValueError, match="unknown rule"):
        lint_main([str(tmp_path / "ok.py"), "--rules", "nope"])


def test_syntax_error_is_a_finding():
    errs = _errors("def f(:\n", "src/repro/x.py")
    assert len(errs) == 1 and errs[0].rule == "parse"


# ---------------------------------------------------------------------------
# toml_lite

def test_toml_lite_subset():
    data = toml_lite.loads(
        '# comment\n'
        '[[allow]]\n'
        'rule = "wall-clock"  # trailing\n'
        'path = "a # not-a-comment.py"\n'
        'reason = "because"\n'
        '[vmem]\n'
        'target = "lrz:tpu-v5e-pod"\n'
        '[vmem.bounds]\n'
        'd = 8192\n'
        'frac = 0.5\n'
        'flag = true\n')
    assert data["allow"] == [{"rule": "wall-clock",
                              "path": "a # not-a-comment.py",
                              "reason": "because"}]
    assert data["vmem"]["target"] == "lrz:tpu-v5e-pod"
    assert data["vmem"]["bounds"] == {"d": 8192, "frac": 0.5,
                                      "flag": True}


def test_toml_lite_rejects_junk_with_line_numbers():
    with pytest.raises(toml_lite.TomlLiteError, match="line 2"):
        toml_lite.loads('[ok]\nwhat even is this\n')
    with pytest.raises(toml_lite.TomlLiteError, match="line 1"):
        toml_lite.loads('k = [1, 2]\n')


# ---------------------------------------------------------------------------
# validate_bench: wall_* keys are rejected in gated positions

def test_validate_bench_rejects_wall_keys():
    data = validate_bench.parse_strict(
        (REPO / "BENCH_serving.json").read_text())
    assert validate_bench.check(data) == []
    data["cells"]["paged_static"]["wall_latency_s"] = 1.23
    problems = "\n".join(validate_bench.check(data))
    assert "wall_latency_s" in problems and "gated position" in problems
