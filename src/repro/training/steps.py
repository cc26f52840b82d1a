"""Step builders: train (grad-accumulation scan), prefill, decode.

``build_train_step`` assembles the full training step from a model, an
optimizer and a DeploymentPlan: microbatch scan (gradient accumulation),
optional error-feedback int8 gradient compression, LR schedule, optimizer
update.  The returned function is pure and jit/pjit-able; the EASEY
BuildService owns jit+sharding+donation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.plan import DeploymentPlan
from repro.optim.schedule import warmup_cosine


def _accum_dtype(plan):
    return jnp.bfloat16 if plan.grad_accum_dtype == "bfloat16" else jnp.float32


def _ef_int8(g, err):
    """Error-feedback int8 quantization of a gradient contribution — models
    compressed cross-replica reduction (wire bytes /4 vs fp32)."""
    x = g.astype(jnp.float32) + err
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-20) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    deq = q * scale
    return deq, (x - deq)


def build_train_step(model, opt, plan: DeploymentPlan, mesh=None,
                     peak_lr: float = 3e-4, warmup_steps: int = 100,
                     total_steps: int = 10_000, param_specs=None):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt", "ef" (optional), "step"}.
    param_specs: optional NamedSharding tree for the params — used to pin
    the gradient-accumulation scan carry (perf iteration I6: an
    unconstrained carry is materialized REPLICATED by XLA, turning the
    per-microbatch gradient reduction into full all-reduces and blowing
    fp32 grad buffers up by the data-axis factor).
    """
    M = plan.microbatches
    use_ef = plan.grad_compression == "ef_int8"

    def loss_fn(params, mb):
        return model.loss(params, mb, mesh)

    def _pin(gtree):
        if param_specs is None:
            return gtree
        return jax.tree.map(jax.lax.with_sharding_constraint, gtree,
                            param_specs)

    def train_step(state, batch):
        params = state["params"]
        acc_dt = _accum_dtype(plan)

        if M == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = _pin(grads)
        else:
            def split(x):
                b = x.shape[0]
                assert b % M == 0, (b, M)
                return x.reshape(M, b // M, *x.shape[1:])

            micro = jax.tree.map(split, batch)
            g0 = _pin(jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt),
                                   params))

            def body(carry, mb):
                gsum, lsum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                gsum = _pin(jax.tree.map(
                    lambda a, b_: a + b_.astype(acc_dt), gsum, g))
                return (gsum, lsum + l), None

            (gacc, lsum), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), micro)
            grads = jax.tree.map(lambda g: (g / M).astype(jnp.float32), gacc)
            loss = lsum / M
            metrics = {"loss": loss}

        if use_ef:
            pairs = jax.tree.map(_ef_int8, grads, state["ef"])
            grads = jax.tree.map(lambda pr: pr[0], pairs,
                                 is_leaf=lambda x: isinstance(x, tuple))
            new_ef = jax.tree.map(lambda pr: pr[1], pairs,
                                  is_leaf=lambda x: isinstance(x, tuple))

        lr = warmup_cosine(state["step"], peak_lr=peak_lr,
                           warmup_steps=warmup_steps, total_steps=total_steps)
        new_params, new_opt, opt_metrics = opt.update(
            grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if use_ef:
            new_state["ef"] = new_ef
        metrics = dict(metrics, lr=lr, **opt_metrics)
        return new_state, metrics

    return train_step


def init_train_state(model, opt, params, plan: DeploymentPlan):
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if plan.grad_compression == "ef_int8":
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def train_state_table(model, opt, plan: DeploymentPlan):
    """Declarative (ParamDef) state table — dry-run path, no allocation."""
    from repro.models.params import ParamDef, _map_table
    import dataclasses as dc
    ptable = model.param_table()
    t = {"params": ptable, "opt": opt.state_table(ptable),
         "step": ParamDef((), (), jnp.int32, "zeros")}
    if plan.grad_compression == "ef_int8":
        t["ef"] = _map_table(ptable, lambda d: dc.replace(
            d, dtype=jnp.float32, init="zeros"))
    return t


def build_prefill_step(model, mesh=None):
    def prefill_step(params, batch):
        return model.prefill(params, batch, mesh)
    return prefill_step


def build_prefill_chunk_step(model, mesh=None):
    """Chunked prefill straight into a *contiguous* serving KV pool.

    ``cache`` is the pool's cache tree — K/V shaped ``(layers, num_slots,
    max_len, kv_heads, head_dim)`` plus the per-slot ``index`` vector —
    and ``tokens`` is one bucketed ``(1, c)`` chunk of one request's
    prompt.  The chunk's K/V scatter directly to ``[slot, offset:offset+c)``
    (no intermediate contiguous ``(1, s)`` cache that ``insert`` would
    have to re-scatter), the chunk attends causally over everything the
    slot already holds, and the returned logits sit at the chunk's last
    valid position (``n_valid`` <= c covers bucket padding).  Jittable
    with ``kv_bound`` static (it sizes the slot's KV read-back — a short
    prompt attends its own bucketed prefix, not max_len); the engine
    donates the cache argument.
    """
    def chunk_step(params, cache, tokens, slot, offset, n_valid, kv_bound):
        return model.chunk_prefill(params, cache, tokens, slot, offset,
                                   n_valid, mesh, kv_bound)
    return chunk_step


def build_prefill_chunk_step_paged(model, mesh=None):
    """Chunked prefill straight into a *paged* serving KV pool.

    Same contract as ``build_prefill_chunk_step``, but K/V are the page
    pool ``(layers, num_pages, page_size, rows, lanes)`` (a token's K or V
    heads as ``serving/pool.page_rows`` stores them) and
    ``pages_row`` is the slot's ``(max_pages,)`` page-table row: chunk
    token at global position j lands in page ``pages_row[j // page_size]``
    at offset ``j % page_size`` — its final resting place, one write,
    in place in the pool the layer scan carries (as the decode step).
    Pages must be reserved by the pool before the call; rows past the
    reserved region (bucket padding) fall into the junk page 0.
    """
    def chunk_step(params, cache, tokens, slot, offset, n_valid, kv_bound,
                   pages_row):
        return model.chunk_prefill(params, cache, tokens, slot, offset,
                                   n_valid, mesh, kv_bound,
                                   pages_row=pages_row)
    return chunk_step


def build_decode_step(model, mesh=None):
    def decode_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens, mesh)
    return decode_step


def build_decode_step_slots(model, mesh=None):
    """Slot-wise decode for the continuous-batching serving engine.

    ``cache['index']`` is a per-slot length vector (one row per KV-pool
    slot) and ``active`` flags the slots holding a live request.  Inactive
    slots still ride through the batched matmuls — the fixed price of
    slot-indexed batching — but their lengths do not advance, so a freed
    slot can be re-prefilled between steps without disturbing its
    neighbours.  Jittable; the engine donates the cache argument.
    """
    def decode_step(params, cache, tokens, active):
        logits, new_cache = model.decode_step(params, cache, tokens, mesh)
        keep = active.astype(bool)
        new_index = jnp.where(keep, new_cache["index"], cache["index"])
        return logits, dict(new_cache, index=new_index)
    return decode_step


def build_decode_step_slots_paged(model, mesh=None, use_kernel: bool = False):
    """Slot-wise decode over a *paged* KV pool (PagedKVCachePool).

    Same contract as ``build_decode_step_slots``, but the cache's K/V are
    a page pool ``(layers, num_pages, page_size, rows, lanes)`` (or one
    latent pool, ``serving/pool.page_stores``) and
    the per-slot ``(num_slots, max_pages)`` int32 page table arrives as an
    extra argument each step (the pool keeps it on the host so page
    alloc/free never touches the device).  The model reads and writes K/V
    through the table; a slot whose table row is zeroed (freed) scatters
    its dead write into the reserved junk page 0 (of each layer).
    Jittable; the engine donates the cache argument only — the page table
    is tiny and re-uploaded per step.  The layer scan carries the whole
    pool: each layer writes its new K/V into it in place and reads its
    own layer where it lies, so with the cache donated no part of the pool
    is copied.

    use_kernel=True swaps the gather-then-attend read for the fused
    Pallas paged-attention kernel (kernels/paged_attention.py): the page
    table is walked inside the kernel, which DMAs the layer's pages out
    of the whole pool, so the materialized
    (slots, max_pages*page_size, K, dh) read never hits HBM.  The flag is
    STATIC — it is closed over and inserted into the cache dict inside
    the traced function, never at the jit boundary, so cache pytree
    structure (and donation) is unchanged.
    """
    def decode_step(params, cache, tokens, active, pages):
        keep = active.astype(bool)
        # inactive rows (freed slots, or slots mid-prefill whose device
        # index is stale) must not write through their page table: with a
        # shared-prefix cache a stale-index write would land inside a
        # read-only page other requests attend, so their rows divert to
        # the reserved junk page 0 — same place zeroed rows already write
        safe_pages = jnp.where(keep[:, None], pages, 0)
        dcache = dict(cache, pages=safe_pages)
        if use_kernel:
            dcache["use_kernel"] = True
        logits, new_cache = model.decode_step(params, dcache, tokens, mesh)
        new_index = jnp.where(keep, new_cache["index"], cache["index"])
        return logits, _pool_cache(new_cache, new_index)
    return decode_step


def _pool_cache(new_cache, index):
    """The pool's cache tree from a paged step's: its stores (K and V, or
    the latent rows, and any counters) and ``index``."""
    return dict({n: c for n, c in new_cache.items() if n != "pages"},
                index=index)


def build_verify_step_slots(model, mesh=None):
    """Speculative VERIFY step over a contiguous slot pool.

    ``tokens`` is ``(num_slots, k+1)`` — each row's pending token followed
    by its k drafted tokens — and the step returns logits at **every**
    speculated position ``(num_slots, k+1, vocab)``, scoring all of them
    against pool KV in one jitted call (the multi-position generalization
    of the single-token decode scatter in ``models/layers.attention``).
    Positions past a slot's capacity drop harmlessly; rejected-draft KV is
    overwritten by the next step before any causal mask admits it.

    The returned cache keeps ``index`` UNCHANGED: how many of the k+1
    positions became real tokens is the host's acceptance decision, so the
    scheduler re-uploads its post-acceptance length mirror
    (``pool.sync_index``) instead of trusting a device-side +k+1.
    """
    def verify_step(params, cache, tokens, active):
        logits, new_cache = model.decode_step(params, cache, tokens, mesh)
        return logits, dict(new_cache, index=cache["index"])
    return verify_step


def build_verify_step_slots_paged(model, mesh=None):
    """Speculative VERIFY step over a paged KV pool.

    Same contract as ``build_verify_step_slots`` plus the page table
    argument; inactive rows divert through junk page 0 exactly like
    ``build_decode_step_slots_paged``, and per-position page lookup keeps
    the same ok-guard, so a burst past a slot's page-run capacity can
    never scribble into a (possibly prefix-shared) live page.  The fused
    Pallas kernel is single-token-only, so verify always reads through
    the gather path, which agrees with the kernel to the activation
    dtype's rounding (tests/test_kernels_paged.py).
    ``index`` stays host-authoritative (see ``build_verify_step_slots``).
    """
    def verify_step(params, cache, tokens, active, pages):
        keep = active.astype(bool)
        safe_pages = jnp.where(keep[:, None], pages, 0)
        dcache = dict(cache, pages=safe_pages)
        logits, new_cache = model.decode_step(params, dcache, tokens, mesh)
        return logits, _pool_cache(new_cache, cache["index"])
    return verify_step
