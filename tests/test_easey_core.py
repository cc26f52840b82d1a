"""EASEY core: Appfile, JobSpec (paper §3), batch synthesis (Alg. 1),
package integrity, middleware staging, job state machine, tuner."""

import json
import tarfile
from pathlib import Path

import pytest

from repro.core.appspec import AppSpec, parse_appfile
from repro.core.batch import make_batch, pbs_batch, slurm_batch
from repro.core.jobs import Job, JobState, LocalScheduler
from repro.core.jobspec import lulesh_example, parse_jobspec
from repro.core.target import get_target
from repro.core.tuning import tune
from repro.configs import SHAPES, get_config


# ---------------------------------------------------------------- Appfile

APPFILE = """\
FROM arch:deepseek-7b
SHAPE train_4k
###include_local_kernels###
###include_local_collectives###
RUN train --steps 50
"""


def test_appfile_roundtrip():
    spec = parse_appfile(APPFILE)
    assert spec.arch == "deepseek-7b"
    assert spec.shape == "train_4k"
    assert spec.run == "train --steps 50"
    spec2 = parse_appfile(spec.to_appfile())
    assert spec2.arch == spec.arch and spec2.shape == spec.shape


def test_appfile_rejects_unknown_directive():
    with pytest.raises(ValueError, match="unknown directive"):
        parse_appfile("FROM arch:deepseek-7b\nSHAPE train_4k\n###bogus###\n")


def test_appfile_accepts_paper_mpi_hook():
    spec = parse_appfile(
        "FROM arch:deepseek-7b\nSHAPE train_4k\n###includelocalmpi###\n")
    assert "###includelocalmpi###" in spec.directives


def test_appspec_hash_stable():
    a = AppSpec("deepseek-7b", "train_4k")
    b = AppSpec("deepseek-7b", "train_4k")
    assert a.content_hash() == b.content_hash()
    c = AppSpec("deepseek-7b", "decode_32k")
    assert a.content_hash() != c.content_hash()


# ---------------------------------------------------------------- JobSpec

def test_lulesh_listing_1_5_parses():
    spec = parse_jobspec(lulesh_example())
    assert spec.name == "lulesh_dash"
    assert spec.deployment.nodes == 46
    assert spec.deployment.tasks_per_node == 48
    assert spec.deployment.clocktime == "06:00:00"
    assert spec.executions[0].kind == "mpi"
    assert spec.executions[0].mpi_tasks == 2197  # 13^3 cores, paper Table 1
    assert "lulesh.dash -i 1000 -s 13" in spec.executions[0].command


def test_jobspec_id_hash_on_submission():
    spec = parse_jobspec({"job": {"name": "j"}})
    assert spec.job_id == ""
    jid = spec.ensure_id()
    assert len(jid) == 12 and spec.ensure_id() == jid


def test_gridftp_planned_next_release():
    with pytest.raises(NotImplementedError, match="next release"):
        parse_jobspec({"job": {"name": "x"}, "data": {"input": [
            {"source": "gsiftp://x/y", "protocol": "gridftp"}]}})


# ------------------------------------------------------------- batch files

def test_slurm_batch_golden():
    spec = parse_jobspec(lulesh_example())
    text = slurm_batch(spec, workdir="/scratch/j1")
    assert "#SBATCH --job-name=lulesh_dash" in text
    assert "#SBATCH --nodes=46" in text
    assert "#SBATCH --ntasks-per-node=48" in text
    assert "#SBATCH --time=06:00:00" in text
    assert "#SBATCH --mail-user=hoeb@mnm-team.org" in text
    assert "srun --ntasks=2197" in text
    assert "cd /scratch/j1" in text


def test_pbs_batch_golden():
    spec = parse_jobspec(lulesh_example())
    text = pbs_batch(spec)
    assert "#PBS -N lulesh_dash" in text
    assert "#PBS -l nodes=46:ppn=48" in text
    assert "mpirun -np 2197" in text


def test_unsupported_scheduler_matches_paper():
    spec = parse_jobspec({"job": {"name": "x"}})
    with pytest.raises(ValueError, match="not supported so far"):
        make_batch(spec, "lsf")


# ------------------------------------------------------------ job machine

def test_job_state_transitions():
    j = Job("id", "n")
    j.transition(JobState.RUNNING)
    j.transition(JobState.FAILED)
    j.transition(JobState.PENDING)  # requeue allowed
    with pytest.raises(ValueError):
        Job("id2", "n").transition(JobState.FINISHED)


def test_scheduler_runs_and_requeues():
    sched = LocalScheduler()
    calls = []

    def fn(job):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("boom")
        return 42

    jid = sched.submit(fn, "flaky")
    assert sched.status(jid) is JobState.FAILED
    assert "boom" in sched.logs(jid)[1]
    sched.requeue(jid)
    assert sched.status(jid) is JobState.FINISHED
    assert sched.result(jid) == 42
    assert sched.jobs[jid].restarts == 1


# ------------------------------------------------------------------ tuner

def test_tuner_nemotron_needs_8bit_moments():
    plan = tune(get_config("nemotron-4-340b"), SHAPES["train_4k"],
                get_target("lrz:tpu-v5e-pod"))
    assert plan.optimizer == "adamw8bit"
    assert plan.microbatches >= 8


def test_tuner_small_model_keeps_fp32():
    plan = tune(get_config("stablelm-1.6b"), SHAPES["train_4k"],
                get_target("lrz:tpu-v5e-pod"))
    assert plan.optimizer == "adamw"


def test_tuner_decode_no_remat():
    plan = tune(get_config("deepseek-7b"), SHAPES["decode_32k"],
                get_target("lrz:tpu-v5e-pod"))
    assert plan.remat_policy == "none"
    assert plan.microbatches == 1


def test_plan_json_roundtrip():
    from repro.core.plan import DeploymentPlan
    plan = tune(get_config("dbrx-132b"), SHAPES["train_4k"],
                get_target("lrz:tpu-v5e-2pod"))
    plan2 = DeploymentPlan.from_json(plan.to_json())
    assert plan2.mesh_shape == (2, 16, 16)
    assert plan2.optimizer == plan.optimizer
    assert "EASEY tuning report" in plan2.report()


# --------------------------------------------------- tuner: replica split

def _serve_plan(replicas: int):
    from repro.configs.base import ShapeConfig
    return tune(get_config("deepseek-7b"),
                ShapeConfig("d", 32768, 4096, "decode",
                            serve_replicas=replicas),
                get_target("lrz:tpu-v5e-pod"))


def test_tuner_splits_serve_budget_per_replica():
    """Per-replica slot/page counts shrink as the fleet grows: N replicas
    share one HBM budget, so each gets ~1/N of the KV pool."""
    plans = {n: _serve_plan(n) for n in (1, 2, 4)}
    assert plans[1].serve_replicas == 1 and plans[4].serve_replicas == 4
    assert plans[1].serve_slots > plans[2].serve_slots > plans[4].serve_slots
    assert plans[1].serve_num_pages > plans[2].serve_num_pages \
        > plans[4].serve_num_pages
    # ~proportional: a 4-way split leaves each replica about a quarter
    assert plans[4].serve_slots <= plans[1].serve_slots // 4 + 1
    assert plans[4].serve_num_pages <= plans[1].serve_num_pages // 4 + 1


def test_tuner_fleet_capacity_within_a_page_per_replica():
    """Splitting the budget loses at most rounding: the fleet's aggregate
    paged capacity stays within one page per replica (plus each replica's
    own reserved junk page) of the unsplit pool."""
    single = _serve_plan(1)
    for n in (2, 4, 8):
        plan = _serve_plan(n)
        fleet = plan.napkin["serve_fleet_tokens"]
        per_replica = (plan.serve_num_pages - 1) * plan.serve_page_size
        assert fleet == n * per_replica
        lost = single.napkin["serve_fleet_tokens"] - fleet
        assert 0 <= lost <= 2 * n * plan.serve_page_size


def test_tuner_replica_split_napkin_renders_and_roundtrips():
    from repro.core.plan import DeploymentPlan
    plan = _serve_plan(3)
    for key in ("serve_fleet_capacity", "serve_fleet_tokens", "serve_pool",
                "serve_pool_paged"):
        assert key in plan.napkin, key
    assert "per replica" in plan.napkin["serve_pool"]
    report = plan.report()
    assert "serve replicas  : 3" in report
    assert "serve_fleet_capacity" in report
    again = DeploymentPlan.from_json(plan.to_json())
    assert again.serve_replicas == 3
    assert again.serve_num_pages == plan.serve_num_pages
    # replicas=1 keeps the original single-engine phrasing
    assert "per replica" not in _serve_plan(1).napkin["serve_pool"]


@pytest.mark.parametrize("platform,kind,count,want", [
    ("cpu", "cpu", 1, "local:cpu"),
    ("tpu", "TPU v5 lite", 1, "local:tpu-v5e"),
    ("tpu", "TPU v5 lite", 4, "local:tpu-v5e-2x2"),
    ("tpu", "TPU v9 imaginary", 1, None),
    ("tpu", "TPU v5 lite", 3, None),
])
def test_target_follows_the_attached_devices(platform, kind, count, want):
    """Entry points default to the attached devices' target; a device the
    registry does not know is an error, never another chip's peaks."""
    import types
    from repro.core.target import target_for_devices
    devs = [types.SimpleNamespace(platform=platform, device_kind=kind)] * count
    if want is None:
        with pytest.raises(KeyError, match="no target"):
            target_for_devices(devs)
        return
    t = target_for_devices(devs)
    assert t.name == want and t.num_chips == count
    if platform == "tpu":
        assert t.kernels == "pallas" and t.hbm_bytes == 16 * 2**30
    assert get_target().name == "local:cpu"     # the tests' own platform


def test_compile_cache_dir_is_the_env_or_a_fixed_checkout_path(
        monkeypatch, tmp_path):
    """The entry points' compile cache: JAX's own variable wins and the
    code sets nothing; unset, it is <checkout>/.jax_cache every time (no
    temp name, pid or time).  jax.config.update is captured, so this test
    never turns the cache on."""
    import jax
    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(cc.CACHE_ENV, str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert calls == []
    monkeypatch.delenv(cc.CACHE_ENV)
    first = cc.enable_compile_cache()
    assert first == cc.enable_compile_cache()
    assert first == str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2


@pytest.mark.parametrize("count", [1, 4])
def test_serving_takes_one_chip_of_the_host(monkeypatch, count):
    """Serving never shards over a mesh: with no target named, the engine
    and the serve CLI take the first attached chip alone, on a four-chip
    host too, while the build/train default takes the whole host."""
    import types

    import jax
    from repro.core.target import serve_target
    from repro.serving import engine as engine_mod
    from repro.serving.router import ReplicaRouter
    devs = [types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
            ] * count
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    assert serve_target().name == "local:tpu-v5e"
    assert serve_target().num_chips == 1
    assert get_target().num_chips == count
    assert serve_target("local:cpu").name == "local:cpu"

    # the engine and the router build with that target by default
    class Built(Exception):
        pass

    def build(self, app, target, **kw):
        raise Built(target.name)

    monkeypatch.setattr(engine_mod.BuildService, "build", build)
    for make in (lambda: engine_mod.ServeEngine(kv_layout="paged"),
                 lambda: ReplicaRouter.build(replicas=1)):
        with pytest.raises(Built, match="^local:tpu-v5e$"):
            make()
