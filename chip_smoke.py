#!/usr/bin/env python3
"""Chip smoke test: the system's main path at full width on a TPU v5e.

    python chip_smoke.py             # one chip: serving + kernel check
    python chip_smoke.py --chips 4   # four chips: sharded train step only

One chip.  Builds a ``ServeEngine`` for ``deepseek-7b`` at its published
widths (d_model 4096, 32 MHA heads, d_ff 11008, vocab 102400, all 30
layers; random weights from ``SEED``) with the paged KV pool and the
fused Pallas decode kernel compiled, then serves 8 greedy requests
(prompts of 128-512 tokens, 32 new tokens each) through
``ServeEngine.run`` twice: a cold pass that compiles and a warm pass that
must repeat its tokens.  It checks that every request finished with
in-vocab tokens, that the served decode step holds the kernel
(``tpu_custom_call``), and that the compiled kernel agrees with
``kernels/ref.paged_attention_ref`` at the same widths within a bf16
tolerance.

Four chips.  Runs only what exists across chips: the BuildService train
step on a 2x2 (data, model) mesh, a few steps of ``deepseek-7b`` at full
width with depth cut to fit one chip, against the same steps on one of
those chips: the losses (forward pass), the gradient norms and the
optimizer's first moment (the data-axis gradient reduction and update).
Both run with float32 activations at full matmul precision.

Each phase prints what it found.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  Without a TPU, outside the repository, or when any phase
fails, the script exits non-zero and prints no such line.

The compilation cache follows ``repro.launch.compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "deepseek-7b"
SEED = 0
# serving: 4 slots, each able to hold a 512-token prompt + 32 new tokens
SLOTS = 4
PROMPT_LENS = (128, 256, 384, 512) * 2
NEW_TOKENS = 32
PAGE_SIZE = 16
# kernel check, same widths as the served model
KERNEL_K, KERNEL_G, KERNEL_DH = 32, 1, 128
# the kernel's output is bf16.  A slot holding many tokens outputs a
# softmax-weighted mean of up to 544 N(0, 1) values, so |out| is ~0.1-0.3
# and below 1, where one bf16 ulp is at most 2**-8 = 0.0039; a one-token
# slot returns its one value (|out| up to ~3) exactly.  The chip measured
# 2**-9 (one ulp in [0.25, 0.5)).  A token masked wrongly moves the
# outputs far more.
KERNEL_ATOL = 5e-3
# four chips: depth kept so params, float32 activations and optimizer
# state fit one chip
MESH_LAYERS = 2
MESH_STEPS = 3
MESH_SEQ, MESH_BATCH = 256, 4
# With bf16 activations this model's gradients carry rounding noise of
# the order of the gradient itself, and the two steps' gradients came out
# uncorrelated.  With float32 activations (the params stay bf16, and so
# do their gradients) the v5e readings were: losses within 8e-8, gradient
# norms within 9.8e-3 and first moments within 3.8e-2 relative.  The
# limits below are about 3x those readings.  The learning rate warms up
# from 0, so the losses check the forward pass only; the gradient norms
# and the first moment m (int8 row-quantized under adamw8bit) check the
# all-reduced gradients.  A gradient taken on half the batch, or halved,
# moves the norms by 50% or more.
MESH_LOSS_RTOL = 1e-5
MESH_GRAD_NORM_RTOL = 3e-2
MESH_MOMENT_RTOL = 1e-1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMonitor:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

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


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    keys = ("bytes_limit", "peak_bytes_in_use", "bytes_in_use")
    return " ".join(f"{k}={stats.get(k, 'n/a')}" for k in keys)


def trace_for(vocab: int):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]


def serve_phase(target: str, monitor: CompileMonitor) -> None:
    """Serve a trace through ServeEngine with the paged pool and kernel
    (``kv_kernel="auto"``: the tuner must pick the kernel for `target`)."""
    import jax
    import numpy as np
    from repro.kernels.ops import interpret_mode
    from repro.models.params import bytes_of
    from repro.serving import ServeEngine

    max_len = max(PROMPT_LENS) + NEW_TOKENS
    # every slot can hold a worst-case request, plus the junk page 0
    num_pages = SLOTS * math.ceil(max_len / PAGE_SIZE) + 1
    t0 = time.perf_counter()
    engine = ServeEngine(arch=ARCH, target=target, num_slots=SLOTS,
                         max_len=max_len, seed=SEED, page_size=PAGE_SIZE,
                         num_pages=num_pages, kv_kernel="auto", log=log)
    jax.block_until_ready(engine.params)
    cfg = engine.cfg
    log(f"arch={ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"param_bytes={bytes_of(engine.params)} "
        f"init_s={time.perf_counter() - t0:.3f}")
    log("memory after init: " + memory_line(jax.devices()[0]))
    log(f"pool: slots={engine.num_slots} pages={engine.num_pages} "
        f"page_size={engine.page_size} max_len={engine.max_len}")
    if engine.kv_kernel != "pallas":
        raise RuntimeError(f"kv_kernel={engine.kv_kernel}, want pallas")
    if jax.default_backend() == "tpu" and interpret_mode():
        raise RuntimeError("the kernel would run interpreted on a TPU")

    reqs = trace_for(cfg.vocab_size)
    runs = []
    for name in ("cold", "warm"):
        c0 = monitor.seconds
        t0 = time.perf_counter()
        stats = engine.run(reqs)
        wall = time.perf_counter() - t0
        runs.append({r.rid: list(r.tokens) for r in stats.results})
        toks = sum(len(t) for t in runs[-1].values())
        log(f"serve {name}: requests={len(stats.results)} tokens={toks} "
            f"decode_steps={stats.decode_steps} "
            f"prefill_chunks={stats.prefill_chunks} wall_s={wall:.3f} "
            f"compile_s={monitor.seconds - c0:.3f}")
    done = runs[0]
    if sorted(done) != [r.rid for r in reqs]:
        raise RuntimeError(f"finished {sorted(done)} of {len(reqs)} requests")
    for rid, toks in done.items():
        if len(toks) != NEW_TOKENS:
            raise RuntimeError(f"request {rid}: {len(toks)} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            raise RuntimeError(f"request {rid}: token out of vocab")
    if runs[1] != done:
        raise RuntimeError("warm pass tokens differ from the cold pass")
    log(f"sample tokens (req 0): {done[0][:8]}")

    # the served decode program, lowered for the pool's shapes
    S = jax.ShapeDtypeStruct
    kv = (cfg.num_layers, engine.num_pages, engine.page_size,
          cfg.num_kv_heads, cfg.head_dim)
    max_pages = math.ceil(engine.max_len / engine.page_size)
    cache = {"k": S(kv, cfg.activation_dtype),
             "v": S(kv, cfg.activation_dtype),
             "index": S((engine.num_slots,), np.int32)}
    hlo = engine._decode.lower(
        engine.params, cache, S((engine.num_slots, 1), np.int32),
        S((engine.num_slots,), np.int32),
        S((engine.num_slots, max_pages), np.int32)).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"decode step: tpu_custom_call={has_kernel}")
    if jax.default_backend() == "tpu" and not has_kernel:
        raise RuntimeError("compiled decode step holds no Pallas kernel")
    log("memory: " + memory_line(jax.devices()[0]))


def kernel_phase() -> None:
    """Compiled paged-decode kernel vs the gather-then-attend reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.ops import interpret_mode, paged_attention

    K, G, dh = KERNEL_K, KERNEL_G, KERNEL_DH
    max_len = max(PROMPT_LENS) + NEW_TOKENS
    max_pages = math.ceil(max_len / PAGE_SIZE)
    # full, partial, one-token and freed (length 0) slots
    lens = [max_len, max_len // 2 + 3, 1, 0][:SLOTS]
    held = [math.ceil(n / PAGE_SIZE) for n in lens]
    num_pages = sum(held) + 1
    order = np.random.default_rng(SEED).permutation(
        np.arange(1, num_pages, dtype=np.int32))
    table = np.zeros((SLOTS, max_pages), np.int32)
    i = 0
    for s, h in enumerate(held):
        table[s, :h] = order[i:i + h]
        i += h
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (SLOTS, K * G, dh), jnp.bfloat16)
    kp = jax.random.normal(kk, (num_pages, PAGE_SIZE, K, dh), jnp.bfloat16)
    vp = jax.random.normal(kv, (num_pages, PAGE_SIZE, K, dh), jnp.bfloat16)
    table, kv_len = jnp.asarray(table), jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kp, vp, table, kv_len)
    want = jax.jit(ref.paged_attention_ref)(q, kp, vp, table, kv_len)
    out = np.asarray(out, np.float32)
    err = float(np.max(np.abs(out - np.asarray(want, np.float32))))
    log(f"kernel: slots={SLOTS} K={K} G={G} dh={dh} page_size={PAGE_SIZE} "
        f"max_pages={max_pages} interpret={interpret_mode()} "
        f"max_abs_out={float(np.max(np.abs(out))):.6g} "
        f"max_abs_err={err:.6g} atol={KERNEL_ATOL}")
    if not err <= KERNEL_ATOL:
        raise RuntimeError(f"kernel differs from the reference by {err}")
    if np.any(out[np.asarray(lens) == 0] != 0.0):
        raise RuntimeError("a freed slot read the junk page")


def _first_moment(opt_state) -> list:
    """The optimizer's first moment m, one float32 array per leaf
    (dequantized for the int8 variant)."""
    import jax
    import numpy as np
    is_mom = lambda x: isinstance(x, dict) and ("m" in x or "m_q" in x)
    return [np.asarray(m["m"], np.float32) if "m" in m else
            np.asarray(m["m_q"], np.float32) * np.asarray(m["m_s"])
            for m in jax.tree.leaves(opt_state["moments"], is_leaf=is_mom)]


def _train_steps(app, target, overrides, hlo: bool = False):
    """MESH_STEPS BuildService train steps on `target`: (build result,
    losses, gradient norms, first moment, compiled step text if `hlo`)."""
    import jax
    from repro.core.build import BuildService
    from repro.data.pipeline import DataPipeline
    from repro.models.params import init_params
    from repro.models.transformer import model_for
    from repro.optim import make_optimizer
    from repro.training.steps import init_train_state

    res = BuildService().build(app, target, overrides=overrides, lower=False)
    model = model_for(app.model_config, remat=res.plan.remat_policy)
    opt = make_optimizer(res.plan.optimizer)
    state = init_train_state(
        model, opt, init_params(model.param_table(), jax.random.PRNGKey(SEED)),
        res.plan)
    state = jax.device_put(state, res.in_shardings[0])
    pipe = DataPipeline(model, app.shape_config, seed=SEED, mesh=res.mesh)
    step = jax.jit(res.step_fn, in_shardings=res.in_shardings,
                   out_shardings=res.out_shardings, donate_argnums=(0,))
    losses, norms = [], []
    for i in range(MESH_STEPS):
        state, metrics = step(state, pipe.batch_at(i))
        losses.append(float(metrics["loss"]))
        norms.append(float(metrics["grad_norm"]))
    text = step.lower(state, pipe.batch_at(MESH_STEPS)).compile().as_text() \
        if hlo else ""
    return res, losses, norms, _first_moment(state["opt"]), text


def mesh_phase(mesh_target: str, one_target: str) -> None:
    """Sharded train step on the mesh vs the same steps on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.appspec import AppSpec

    app = AppSpec(arch=ARCH, shape="train_4k",
                  shape_overrides={"seq_len": MESH_SEQ,
                                   "global_batch": MESH_BATCH},
                  overrides={"num_layers": MESH_LAYERS,
                             "activation_dtype": jnp.float32},
                  run=f"train --steps {MESH_STEPS}")
    with jax.default_matmul_precision("highest"):
        one, one_losses, one_norms, one_m, _ = _train_steps(
            app, one_target, None)
        plan = one.plan
        # the same plan on the mesh: only the sharding may differ
        pinned = {k: getattr(plan, k) for k in (
            "optimizer", "microbatches", "remat_policy", "grad_accum_dtype",
            "sequence_parallel", "grad_compression")}
        log(f"train plan (pinned on both): {pinned}")
        log("memory after one-chip steps: " + memory_line(jax.devices()[0]))
        mesh, mesh_losses, mesh_norms, mesh_m, hlo = _train_steps(
            app, mesh_target, pinned, hlo=True)
    collectives = [op for op in ("all-reduce", "all-gather",
                                 "reduce-scatter", "all-to-all")
                   if op in hlo]
    diff = math.sqrt(sum(float(np.sum(np.square(a - b)))
                         for a, b in zip(mesh_m, one_m)))
    norm = math.sqrt(sum(float(np.sum(np.square(b))) for b in one_m))
    moment_err = diff / norm
    log(f"mesh {dict(mesh.mesh.shape)} on "
        f"{len(mesh.mesh.devices.flat)} devices, layers={MESH_LAYERS} "
        f"d_model={app.model_config.d_model} seq={MESH_SEQ} "
        f"batch={MESH_BATCH} activations=float32: "
        f"collectives={collectives}")
    log(f"losses mesh={mesh_losses} one_chip={one_losses}")
    log(f"grad_norms mesh={mesh_norms} one_chip={one_norms}")
    log(f"first moment: |m_mesh - m_one| / |m_one| = {moment_err:.6g} "
        f"(|m_one| = {norm:.6g}) rtol={MESH_MOMENT_RTOL}")
    if not all(np.isfinite(mesh_losses + mesh_norms)):
        raise RuntimeError(
            f"non-finite mesh losses {mesh_losses} or norms {mesh_norms}")
    if not collectives:
        raise RuntimeError("sharded train step holds no collective")
    np.testing.assert_allclose(mesh_losses, one_losses, rtol=MESH_LOSS_RTOL)
    np.testing.assert_allclose(mesh_norms, one_norms,
                               rtol=MESH_GRAD_NORM_RTOL)
    if not moment_err <= MESH_MOMENT_RTOL:
        raise RuntimeError(f"first moments differ by {moment_err:.6g}")
    for d in jax.devices()[:len(mesh.mesh.devices.flat)]:
        log(f"memory {d.id}: " + memory_line(d))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: serve on one chip; 4: sharded train step on a "
                        "2x2 mesh against one chip")
    a = p.parse_args(argv)
    try:
        from repro.core.target import target_for_devices
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    monitor = CompileMonitor()
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__} platform={dev.platform} "
        f"kind={dev.device_kind!r} count={len(devices)} "
        f"compile_cache={cache_dir}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < a.chips:
        print(f"chip_smoke: --chips {a.chips} but {len(devices)} devices",
              file=sys.stderr)
        return 1
    try:
        one_chip = target_for_devices(devices[:1]).name
        if a.chips == 4:
            mesh_phase(target_for_devices(devices[:4]).name, one_chip)
        else:
            serve_phase(one_chip, monitor)
            kernel_phase()
        log(monitor.line())
    except Exception:  # noqa: BLE001 — any phase failing fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
