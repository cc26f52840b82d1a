"""End-to-end training driver (EASEY RUN command `train ...`).

Wires every substrate together: BuildService (tuned, jitted step) ->
DataPipeline (deterministic, restart-safe) -> Checkpointer (atomic, async)
-> fault tolerance (failure injection + restart policy + straggler
monitor).  Runnable on the CPU debug target with smoke archs; the exact
same code path lowers for the production meshes.
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core.appspec import AppSpec
from repro.core.build import BuildService
from repro.core.target import get_target
from repro.data.pipeline import DataPipeline
from repro.models.transformer import model_for
from repro.runtime.fault_tolerance import (FailureInjector, StragglerMonitor,
                                           run_with_restarts)
from repro.training.steps import init_train_state


def train_main(arch: str = "deepseek-7b-smoke", steps: int = 20,
               target: str | None = None, seq_len: int = 64,
               global_batch: int = 4, ckpt_dir: str | None = None,
               ckpt_every: int = 5, async_ckpt: bool = True,
               fail_at: tuple[int, ...] = (), resume: bool = True,
               log=print, seed: int = 0) -> dict:
    app = AppSpec(arch=arch, shape="train_4k",
                  shape_overrides={"seq_len": seq_len,
                                   "global_batch": global_batch},
                  run=f"train --steps {steps}")
    tgt = get_target(target)
    svc = BuildService()
    result = svc.build(app, tgt, lower=False)
    model = model_for(app.model_config, remat=result.plan.remat_policy)
    from repro.optim import make_optimizer
    opt = make_optimizer(result.plan.optimizer)

    jit_step = jax.jit(result.step_fn, donate_argnums=(0,))
    pipeline = DataPipeline(model, app.shape_config, seed=seed,
                            mesh=None if tgt.num_chips == 1 else result.mesh)
    ckpt = Checkpointer(ckpt_dir, keep=3, async_writes=async_ckpt) \
        if ckpt_dir else None
    injector = FailureInjector(fail_at_steps=tuple(fail_at))
    straggler = StragglerMonitor()

    rng = jax.random.PRNGKey(seed)
    losses: dict[int, float] = {}

    def loop(start_step: int) -> int:
        from repro.models.params import init_params
        params = init_params(result.tables["params"], rng)
        state = init_train_state(model, opt, params, result.plan)
        if ckpt and start_step > 0:
            state, at = ckpt.restore(state)
            log(f"[train] restored checkpoint step {at}")
        step = start_step
        while step < steps:
            injector.check(step)
            batch = pipeline.batch_at(step)
            t0 = time.perf_counter()
            state, metrics = jit_step(state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            if straggler.observe(step, dt):
                log(f"[train] step {step}: straggler ({dt:.3f}s)")
            losses[step] = loss
            if step % max(steps // 10, 1) == 0:
                log(f"[train] step {step} loss={loss:.4f} "
                    f"({dt*1e3:.1f} ms)")
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save(step, state)
            step += 1
        if ckpt:
            ckpt.save(steps - 1, state)
            ckpt.wait()
        return step

    if resume and ckpt:
        stats = run_with_restarts(loop, checkpointer=ckpt, logger=log)
    else:
        stats = {"final_step": loop(0), "restarts": 0}

    loss_curve = [losses[s] for s in sorted(losses)]
    return {
        "arch": arch, "steps": stats["final_step"],
        "restarts": stats["restarts"],
        "first_loss": loss_curve[0] if loss_curve else float("nan"),
        "final_loss": loss_curve[-1] if loss_curve else float("nan"),
        "stragglers": len(straggler.flagged),
        "plan": result.plan,
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="deepseek-7b-smoke")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--target", default=None,
                   help="registered target (default: the attached devices)")
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--global-batch", type=int, default=4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--fail-at", type=int, nargs="*", default=[])
    a = p.parse_args(argv)
    out = train_main(arch=a.arch, steps=a.steps, target=a.target,
                     seq_len=a.seq_len, global_batch=a.global_batch,
                     ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every,
                     fail_at=tuple(a.fail_at))
    print(f"final: loss {out['first_loss']:.4f} -> {out['final_loss']:.4f} "
          f"in {out['steps']} steps ({out['restarts']} restarts)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
