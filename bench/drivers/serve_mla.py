"""The serving driver for DeepSeek-V2 cells: latent attention and
DeepSeekMoE through ``ServeEngine``.

It runs ``bench/drivers/serve.py``'s ``drive`` — the same engine, pool,
scheduler, warm-up, window, end-to-end numbers and check — with this
model's parts in place of the dense decoder's, swapped into a copy of
that module loaded for the run:

* ``program_arch``: the program's preset named in the file's ``program``
  (``repro.configs``' ``deepseek-v2-lite``) with its ``overrides`` (the
  experts held), registered under ``<name>-<held>of<experts>`` and held
  to the file's published widths;
* ``bench/weights_mla.py`` makes the weights and ``bench/reference_mla.py``
  is the reference (``readings`` and the float8 control are
  ``bench/reference.py``'s); the configuration file holds the published
  keys at its top level.

It adds, outside the timed window:

* the routing counters the program keeps on the device
  (``ServeStats.expert_tokens``, ``held_pick_share``), logged;
* in a traced run, the decode step's device time per scope
  (``rec["scopes"]``, ``bench/program_trace.scopes``), with the scopes of
  the program's latent attention and expert layer (``mla_q``, ``moe``,
  ``router``, ``experts``, ``shared``) added to ``program_trace.SCOPES``
  while the trace is read; the op names come from the decode step's
  compiled HLO text.

Beyond ``serve.py``'s contract it touches ``ServeEngine._decode`` (the
jitted decode step, compiled again from the cache for its HLO text),
``serving.pool.page_stores`` and ``Scheduler.stats``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp

from bench import program_trace as pt, reference_mla, weights_mla

PROGRAM_SCOPES = ("mla_q", "moe", "router", "experts", "shared")


def _serve_module():
    path = Path(__file__).resolve().parent / "serve.py"
    spec = importlib.util.spec_from_file_location("bench_serve_for_mla", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_arch(config: dict) -> str:
    """The program's name for the cut configuration, registered from the
    file's ``program`` section; raises when the program's sizes differ
    from the file's."""
    from repro.configs.base import ARCHS, get_config, register
    prog = config["program"]
    m = reference_mla.model_dims(config)
    name = f"{config['name']}-{m['held']}of{m['experts']}"
    if name not in ARCHS:
        cfg = get_config(prog["arch"]).replace(name=name,
                                               **prog.get("overrides", {}))
        register(cfg, cfg.replace(name=name + "-unused-smoke"))
    cfg = get_config(name)
    got = {"d": cfg.d_model, "heads": cfg.num_heads,
           "rank": cfg.kv_lora_rank, "nope": cfg.qk_nope_head_dim,
           "rope": cfg.qk_rope_head_dim, "v": cfg.v_head_dim,
           "d_ff": cfg.d_ff, "moe_d_ff": cfg.moe_d_ff,
           "experts": cfg.num_experts, "held": cfg.experts_held,
           "offset": cfg.expert_offset, "top_k": cfg.experts_per_token,
           "shared": cfg.shared_experts, "norm_topk": cfg.norm_topk,
           "dense_layers": cfg.first_dense_layers, "layers": cfg.num_layers,
           "vocab": cfg.vocab_size, "eps": 1e-6,
           "rope_theta": cfg.rope_theta,
           "yarn_factor": cfg.rope_yarn.factor,
           "yarn_original": cfg.rope_yarn.original_max_position,
           "yarn_beta_fast": cfg.rope_yarn.beta_fast,
           "yarn_beta_slow": cfg.rope_yarn.beta_slow,
           "yarn_mscale": cfg.rope_yarn.mscale,
           "yarn_mscale_all_dim": cfg.rope_yarn.mscale_all_dim}
    want = {k: m[k] for k in got}
    # the program does not scale its gates: it serves
    # routed_scaling_factor 1 only
    if got != want or m["routed_scale"] != 1.0 or cfg.tie_embeddings \
            or cfg.family != "moe" or cfg.norm != "rmsnorm":
        raise ValueError(f"program config {name} differs from the "
                         f"published one: {got} vs {want}")
    return name


def drive(spec: dict, seed: int, seconds: float, trace: bool, *,
          t_start: float, peaks: dict, monitor, log) -> dict:
    """One run of the cell (``serve.drive``'s record, plus ``scopes`` in a
    traced run)."""
    import repro.serving.engine as engine_mod
    import repro.serving.scheduler as sched_mod
    from repro.serving.pool import page_stores

    serve = _serve_module()
    engines, stats = [], []

    class Engine(engine_mod.ServeEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            engines.append(self)

    class Scheduler(sched_mod.Scheduler):
        def stats(self):
            stats.append(super().stats())
            return stats[-1]

    loaded = []

    def load_program_events(trace_dir):
        """The trace's events with the decode step's op scopes."""
        eng = engines[0]
        stores = jax.eval_shape(
            lambda: page_stores(eng.cfg, eng.num_pages, eng.page_size))
        slots = eng.num_slots
        max_pages = -(-eng.max_len // eng.page_size)
        i32 = jnp.int32
        cache = dict(stores, index=jax.ShapeDtypeStruct((slots,), i32))
        text = eng._decode.lower(
            eng.params, cache, jax.ShapeDtypeStruct((slots, 1), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots, max_pages), i32)).compile().as_text()
        with mock.patch.object(pt, "SCOPES", pt.SCOPES + PROGRAM_SCOPES):
            loaded.extend(pt.load_events(trace_dir, [text]))
        return pt.plain(loaded)

    config = spec["config"]
    # serve.drive reads the published keys under "config"
    run_spec = dict(spec, config=dict(config, config=config))
    # this run's own copy of serve.py keeps these (the record's control
    # runs the reference after drive returns)
    serve.reference = reference_mla
    serve.weights_mod = weights_mla
    serve.program_arch = program_arch
    serve.load_events = load_program_events
    with mock.patch.object(engine_mod, "ServeEngine", Engine), \
            mock.patch.object(sched_mod, "Scheduler", Scheduler):
        rec = serve.drive(run_spec, seed, seconds, trace, t_start=t_start,
                          peaks=peaks, monitor=monitor, log=log)
    # the engine (its weights) goes with the record, not with this module
    engines.clear()
    if stats:
        st = stats[-1]
        log(f"routing: held_pick_share={st.held_pick_share} "
            f"expert_tokens={st.expert_tokens}")
    if loaded:
        rec["scopes"] = pt.scopes(loaded)
        sc = rec["scopes"]
        if sc:
            for path, t in sc["scopes"].items():
                log(f"scope {path}: {t * 1e3:.4f} ms per decode step")
    rec["config"] = config
    return rec
