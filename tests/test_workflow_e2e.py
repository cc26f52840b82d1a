"""End-to-end EASEY workflow (paper Fig. 2 + Algorithm 1): build ->
package -> stage -> submit -> poll -> logs, with real execution, plus the
package-equivalence check behind the paper's negligible-overhead claim."""

import json
import tarfile
from pathlib import Path

import pytest

from repro.core.appspec import AppSpec
from repro.core.build import BuildService
from repro.core.jobspec import parse_jobspec
from repro.core.middleware import Middleware
from repro.core.package import extract_package, read_manifest, write_package
from repro.core.workflow import run_easey


@pytest.fixture(scope="module")
def small_app():
    return AppSpec(arch="stablelm-1.6b-smoke", shape="train_4k",
                   shape_overrides={"seq_len": 32, "global_batch": 2},
                   run="train --steps 3")


def test_build_and_package(tmp_path, small_app):
    res = BuildService().build(small_app, "local:cpu", lower=True)
    pkg = write_package(res, tmp_path)
    assert pkg.exists()
    names = tarfile.open(pkg).getnames()
    assert set(names) == {"manifest.json", "plan.json", "tuning_report.txt",
                          "Appfile", "module.stablehlo.gz"}
    man = read_manifest(pkg)
    assert man["arch"] == "stablelm-1.6b-smoke"
    # extraction verifies the hlo hash (Charliecloud image integrity)
    man2 = extract_package(pkg, tmp_path / "env")
    assert man2["hlo_sha256"] == man["hlo_sha256"]


def test_package_tamper_detected(tmp_path, small_app):
    res = BuildService().build(small_app, "local:cpu", lower=True)
    pkg = write_package(res, tmp_path)
    # corrupt the module
    import io
    with tarfile.open(pkg) as tar:
        members = {m.name: tar.extractfile(m).read() for m in tar}
    members["module.stablehlo.gz"] = b"corrupt"
    with tarfile.open(pkg, "w") as tar:
        for name, data in members.items():
            info = tarfile.TarInfo(name)
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
    with pytest.raises(ValueError, match="integrity"):
        extract_package(pkg, tmp_path / "env2")


def test_algorithm1_data_staging(tmp_path, small_app):
    res = BuildService().build(small_app, "local:cpu", lower=True)
    pkg = write_package(res, tmp_path)
    input_file = tmp_path / "input.bin"
    input_file.write_bytes(b"data!")
    spec = parse_jobspec({
        "job": {"name": "staged"},
        "data": {"input": [{"source": str(input_file), "protocol": "file"}],
                 "mount": {"container-path": "/data"}},
        "deployment": {"nodes": 1},
        "execution": [],
    })
    mw = Middleware(tmp_path / "cluster")
    jid = mw.submit(pkg, spec, runner=None)
    assert mw.status(jid).value == "finished"
    workdir = tmp_path / "cluster" / spec.job_id
    assert (workdir / "data" / "input.bin").read_bytes() == b"data!"
    assert (workdir / "batch.sh").exists()
    assert "#SBATCH" in (workdir / "batch.sh").read_text()


def test_missing_input_fails_staging(tmp_path, small_app):
    res = BuildService().build(small_app, "local:cpu", lower=True)
    pkg = write_package(res, tmp_path)
    spec = parse_jobspec({
        "job": {"name": "bad"},
        "data": {"input": [{"source": "/nonexistent", "protocol": "file"}]},
        "execution": [],
    })
    mw = Middleware(tmp_path / "cluster")
    with pytest.raises(Exception, match="input not found"):
        mw.submit(pkg, spec)


def test_full_easey_run_executes_training(tmp_path, small_app):
    spec = parse_jobspec({
        "job": {"name": "e2e", "mail": "a@b.c"},
        "deployment": {"nodes": 1, "tasks-per-node": 1},
        "execution": [{"serial": {
            "command": "train --steps 3 --seq-len 32 --global-batch 2"}}],
    })
    mw, jid, res = run_easey(small_app, "local:cpu", spec,
                             storage=tmp_path / "s")
    assert mw.status(jid).value == "finished"
    out, err = mw.logs(jid)
    assert "loss" in out
    assert mw.scheduler.result(jid)[0]["steps"] == 3


def test_deployment_equivalence_easey_vs_direct(small_app):
    """The paper's central claim, ported: deploying through EASEY yields
    the SAME program as hand-rolled jit -> on-device overhead ~ 0."""
    import jax
    from repro.models.transformer import model_for
    from repro.models.params import shape_structs
    from repro.optim import make_optimizer
    from repro.training.steps import build_train_step, train_state_table

    res = BuildService().build(small_app, "local:cpu", lower=True)
    easey_hlo = res.lowered.as_text()

    cfg = small_app.model_config
    model = model_for(cfg, remat=res.plan.remat_policy)
    opt = make_optimizer(res.plan.optimizer)
    step = build_train_step(model, opt, res.plan, res.mesh,
                            param_specs=res.in_shardings[0]["params"])
    direct = jax.jit(step, in_shardings=res.in_shardings,
                     out_shardings=res.out_shardings,
                     donate_argnums=(0,)).lower(*res.in_structs)
    assert direct.as_text() == easey_hlo


@pytest.mark.parametrize("command,code", [
    ("train --steps 1 --seq-len 32 --global-batch 2", None),
    ("explode --now", 1),
])
def test_easey_run_cli_exit_code_follows_job_state(tmp_path, small_app,
                                                   command, code, capsys):
    """`easey run` exits 0 only when the job finished: a failed job
    prints state=failed and exits non-zero, so a shell or a CI step sees
    the failure."""
    from repro.core.workflow import _cli
    appfile = tmp_path / "Appfile"
    appfile.write_text(small_app.to_appfile())
    config = tmp_path / "job.json"
    config.write_text(json.dumps({
        "job": {"name": "cli"},
        "execution": [{"serial": {"command": command}}]}))
    argv = ["run", str(appfile), "--target", "local:cpu",
            "--config", str(config)]
    if code is None:
        _cli(argv)
        assert "state=finished" in capsys.readouterr().out
    else:
        with pytest.raises(SystemExit) as exc:
            _cli(argv)
        assert exc.value.code == code
        assert "state=failed" in capsys.readouterr().out
