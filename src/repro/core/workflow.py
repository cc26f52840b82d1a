"""End-to-end EASEY workflow (paper Fig. 2) + the `easey` CLI.

    user --Appfile+JobSpec--> CLIENT (build docker image -> charliecloud tar)
         --package--> MIDDLEWARE (stage, batch, submit) --> jobID
         --poll--> pending/running/finished + logs --> stage-out

`run_easey` wires BuildService -> write_package -> Middleware.submit with a
runner that executes the app's RUN command (train/serve/lulesh) through the
launch layer.
"""

from __future__ import annotations

import argparse
import json
import shlex
import tempfile
from pathlib import Path

from repro.core.appspec import AppSpec, parse_appfile
from repro.core.build import BuildService
from repro.core.jobs import JobState
from repro.core.jobspec import JobSpec, parse_jobspec
from repro.core.middleware import Middleware
from repro.core.package import write_package
from repro.core.target import get_target


def default_runner(job, workdir: Path, spec: JobSpec):
    """Execute the JobSpec's execution commands via the launch layer."""
    from repro.launch.run import run_command  # late import: launch -> core
    results = []
    for ex in spec.executions:
        job.log(f"$ {ex.command}")
        results.append(run_command(ex.command, job=job, workdir=workdir,
                                   spec=spec))
    return results


def run_easey(appspec: AppSpec, target_name: str, jobspec: JobSpec,
              storage: str | Path | None = None, execute: bool = True,
              overrides: dict | None = None):
    """build -> package -> stage -> submit -> wait. Returns (middleware,
    job_id, build_result)."""
    storage = Path(storage) if storage else Path(tempfile.mkdtemp(prefix="easey_"))
    target = get_target(target_name)
    svc = BuildService()
    result = svc.build(appspec, target, overrides=overrides, lower=True)
    pkg = write_package(result, storage / "packages")

    mw = Middleware(storage / "cluster")
    if execute:
        # bind the build result so the runner executes the REAL compiled step
        def runner(job, workdir, spec):
            from repro.launch.run import run_command
            outs = []
            for ex in spec.executions:
                job.log(f"$ {ex.command}")
                outs.append(run_command(ex.command, job=job, workdir=workdir,
                                        spec=spec, build_result=result))
            return outs
    else:
        runner = None
    job_id = mw.submit(pkg, jobspec, runner=runner,
                       scheduler_dialect=target.scheduler
                       if target.scheduler != "local" else "slurm")
    return mw, job_id, result


def _cli(argv=None):
    p = argparse.ArgumentParser(prog="easey")
    sub = p.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("build", help="build an Appfile for a target "
                                     "(paper: easey build Dockerfile --target ...)")
    b.add_argument("appfile")
    b.add_argument("--target", required=True)
    b.add_argument("--out", default="./packages")

    s = sub.add_parser("submit", help="submit a package with a job config")
    s.add_argument("package")
    s.add_argument("--config", required=True)
    s.add_argument("--storage", default="./easey_cluster")

    r = sub.add_parser("run", help="build + submit + execute in one step")
    r.add_argument("appfile")
    r.add_argument("--target", required=True)
    r.add_argument("--config", required=True)

    args = p.parse_args(argv)
    if args.cmd == "build":
        spec = parse_appfile(Path(args.appfile).read_text())
        res = BuildService().build(spec, args.target)
        pkg = write_package(res, args.out)
        print(f"built {pkg}")
        print(res.plan.report())
    elif args.cmd == "submit":
        spec = parse_jobspec(Path(args.config).read_text())
        mw = Middleware(args.storage)
        job_id = mw.submit(args.package, spec)
        print(f"jobID={job_id} state={mw.status(job_id).value}")
    elif args.cmd == "run":
        app = parse_appfile(Path(args.appfile).read_text())
        spec = parse_jobspec(Path(args.config).read_text())
        mw, job_id, _ = run_easey(app, args.target, spec)
        out, err = mw.logs(job_id)
        state = mw.status(job_id)
        print(f"jobID={job_id} state={state.value}")
        print(out)
        if err:
            print("STDERR:", err)
        if state is not JobState.FINISHED:
            raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    _cli()
