"""Decoder-only transformer LM (dense family) + the generic LM interface.

All families implement ``BaseLM``:

    param_table()                  declarative weights (ParamDef tree)
    batch_table(shape)             declarative inputs for a ShapeConfig
    cache_table(batch, max_len)    declarative decode state
    loss(params, batch, mesh)      training loss (mode='full' forward)
    prefill(params, batch, mesh)   build cache + last-position logits
    decode_step(params, cache, tokens, mesh)

Layers are stacked with ``lax.scan`` (compile time on deep models) and
wrapped in ``jax.checkpoint`` per the deployment plan's remat policy.
Decode and chunked prefill carry the whole (layers, ...) KV cache
through the scan and update it in place (``scan_cache``).  A config with
``kv_lora_rank`` attends by latent attention (``models/mla.py``), whose
cache is one latent row per token (``latent``) instead of K and V.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models import mla
from repro.models.params import ParamDef, _map_table
from repro.sharding.rules import shard_constraint


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "full":
        return jax.checkpoint(fn)
    raise ValueError(policy)


# the entries of a serving cache that hold one stack per layer: the layer
# scan carries them and each layer writes its part in place
CARRIED = ("k", "v", "latent", "route_counts")


def stack_defs(defs: dict, n: int) -> dict:
    """Prepend a scanned 'layers' dimension to every ParamDef in a tree."""
    return _map_table(
        defs,
        lambda d: dataclasses.replace(
            d, shape=(n,) + d.shape, logical_axes=("layers",) + d.logical_axes),
    )


class BaseLM:
    def __init__(self, cfg: ModelConfig, remat: str = "dots"):
        self.cfg = cfg
        self.remat = remat

    # -- declarative tables ------------------------------------------------
    def param_table(self) -> dict:
        raise NotImplementedError

    def batch_table(self, shape: ShapeConfig) -> dict:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return {
                "tokens": ParamDef((b, s), ("act_batch", "act_seq"), jnp.int32, "zeros"),
                "labels": ParamDef((b, s), ("act_batch", "act_seq"), jnp.int32, "zeros"),
            }
        if shape.kind == "prefill":
            return {"tokens": ParamDef((b, s), ("act_batch", "act_seq"), jnp.int32, "zeros")}
        # decode: one new token against a cache of length seq_len
        return {"tokens": ParamDef((b, 1), ("act_batch", None), jnp.int32, "zeros")}

    def cache_table(self, batch: int, max_len: int) -> dict:
        raise NotImplementedError

    # -- compute -----------------------------------------------------------
    def loss(self, params, batch, mesh):
        raise NotImplementedError

    def prefill(self, params, batch, mesh):
        raise NotImplementedError

    def decode_step(self, params, cache, tokens, mesh):
        raise NotImplementedError


# ---------------------------------------------------------------------------


class DenseLM(BaseLM):
    """Llama/Mistral/Nemotron/StableLM-style decoder; also the VLM backbone."""

    # ---- tables ----
    def block_defs(self, mlp: dict | None = None) -> dict:
        cfg = self.cfg
        attn = mla.mla_defs(cfg) if cfg.kv_lora_rank else \
            L.attention_defs(cfg)
        d = {"ln1": L.norm_defs(cfg.d_model, cfg.norm),
             "attn": attn,
             "ln2": L.norm_defs(cfg.d_model, cfg.norm),
             "mlp": self.mlp_defs() if mlp is None else mlp}
        return d

    def mlp_defs(self) -> dict:
        return L.mlp_defs(self.cfg)

    def param_table(self) -> dict:
        cfg = self.cfg
        return {
            "embed": L.embed_defs(cfg),
            "blocks": stack_defs(self.block_defs(), cfg.num_layers),
            "ln_f": L.norm_defs(cfg.d_model, cfg.norm),
        }

    def cache_table(self, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        kv = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        ax = ("layers", "act_batch", "act_seq", "act_kv_heads", None)
        return {"k": ParamDef(kv, ax, cfg.activation_dtype, "zeros"),
                "v": ParamDef(kv, ax, cfg.activation_dtype, "zeros"),
                "index": ParamDef((), (), jnp.int32, "zeros")}

    # ---- block ----
    def attend(self, p, h, mesh, positions, mode, cache):
        """The block's attention: (out, new cache)."""
        cfg = self.cfg
        if cfg.kv_lora_rank:
            return mla.attention(p, h, cfg, mesh, positions=positions,
                                 mode=mode, cache=cache)
        return L.attention(p, h, cfg, mesh, positions=positions, mode=mode,
                           cache=cache, window=cfg.window or None)

    def block_apply(self, p, x, mesh, positions, mode, cache):
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        attn_out, new_cache = self.attend(p["attn"], h, mesh, positions,
                                          mode, cache)
        x = x + attn_out
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        with jax.named_scope("mlp"):
            x = x + self.mlp_apply(p["mlp"], h, mesh)
        return x, new_cache

    def mlp_apply(self, p, h, mesh):
        return L.mlp(p, h, self.cfg, mesh)

    def layer_stacks(self, params) -> list:
        """(stacked block params, id of its first layer, block function)
        for each run of like layers, in order."""
        return [(params["blocks"], 0, self.block_apply)]

    def scan_cache(self, params, x, positions, mesh, mode, stores, rest):
        """Decode or chunk over scanned layers with the WHOLE cache in the
        scan carry: ``stores`` are its (layers, ...) stacks (``CARRIED``:
        K and V, or the latent rows, and any counters), and each layer
        gets them all and its layer id, writes its part in place and
        reads its own layer where it lies.  Carried (not scanned as
        xs -> ys), the cache is updated in place — no layer's slice is
        copied out and back, and the donated buffer aliases the step's
        output.  ``rest`` is the layer-invariant rest of the cache.
        Returns (x, new stores)."""
        for blocks, first, apply in self.layer_stacks(params):
            def body(carry, xs, apply=apply):
                y, st = carry
                bp, layer = xs
                y, nc = apply(bp, y, mesh, positions, mode,
                              dict(rest, **st, layer=layer))
                return (y, {n: nc.get(n, st[n]) for n in st}), None

            n = jax.tree.leaves(blocks)[0].shape[0]
            layers = first + jnp.arange(n, dtype=jnp.int32)
            (x, stores), _ = jax.lax.scan(body, (x, stores),
                                          (blocks, layers))
        return x, stores

    # ---- backbone over scanned layers ----
    def backbone(self, params, x, positions, mesh, mode, cache=None):
        blocks = params["blocks"]
        if mode == "full":
            fn = remat_wrap(
                lambda bp, y: self.block_apply(bp, y, mesh, positions, "full", None)[0],
                self.remat)

            def body(carry, bp):
                return fn(bp, carry), None

            x, _ = jax.lax.scan(body, x, blocks)
            return x, None

        if mode in ("decode", "chunk"):
            # the rest of the cache is layer-invariant and closes over the
            # scan body: decode's index (a scalar, or a per-slot vector
            # under continuous batching) and the serving pool's (slots,
            # max_pages) page table; the chunk's offset, valid length and
            # page table row; the STATIC kv_bound and use_kernel (the
            # fused Pallas paged-decode kernel), never part of the jit
            # pytree
            stores = {n: c for n, c in cache.items() if n in CARRIED}
            rest = {n: c for n, c in cache.items() if n not in CARRIED}
            x, stores = self.scan_cache(params, x, positions, mesh, mode,
                                        stores, rest)
            if mode == "chunk":
                return x, stores
            new_cache = dict(stores, index=cache["index"] + x.shape[1])
            if "pages" in cache:
                new_cache["pages"] = cache["pages"]
            return x, new_cache

        # prefill: each layer's fresh cache, stacked
        caches = []
        for blocks, first, apply in self.layer_stacks(params):
            def body_p(carry, bp, apply=apply):
                y, nc = apply(bp, carry, mesh, positions, "prefill", None)
                return y, {n: c for n, c in nc.items() if n in CARRIED}

            x, nc = jax.lax.scan(body_p, x, blocks)
            caches.append(nc)
        new_cache = caches[0] if len(caches) == 1 else \
            jax.tree.map(lambda *c: jnp.concatenate(c), *caches)
        new_cache["index"] = jnp.asarray(x.shape[1], jnp.int32)
        return x, new_cache

    # ---- entry points ----
    # the device trace names the three parts of every step by
    # jax.named_scope: "embed", "layers" (the scan) and "logits"
    def embed_inputs(self, params, batch, mesh, positions):
        with jax.named_scope("embed"):
            return L.embed(params["embed"], batch["tokens"], self.cfg, mesh,
                           positions=positions)

    def logits_from(self, params, x, mesh):
        with jax.named_scope("logits"):
            x = L.apply_norm(params["ln_f"], x, self.cfg.norm)
            return L.unembed(params["embed"], x, self.cfg, mesh)

    def loss(self, params, batch, mesh):
        cfg = self.cfg
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = self.embed_inputs(params, batch, mesh, positions)
        with jax.named_scope("layers"):
            x, _ = self.backbone(params, x, positions, mesh, "full")
        logits = self.logits_from(params, x, mesh)
        loss = L.softmax_xent(logits, batch["labels"],
                              batch.get("loss_mask"))
        return loss, {"loss": loss}

    def prefill(self, params, batch, mesh):
        b, s = batch["tokens"].shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = self.embed_inputs(params, batch, mesh, positions)
        with jax.named_scope("layers"):
            x, cache = self.backbone(params, x, positions, mesh, "prefill")
        # optional batch["last"]: the true final-token position when the
        # prompt is right-padded to a bucketed length (serving re-uses one
        # compiled prefill per bucket; causality keeps rows <= last exact)
        last = batch.get("last")
        x_last = x[:, -1:] if last is None else \
            jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
        logits = self.logits_from(params, x_last, mesh)
        return logits, cache

    def chunk_prefill(self, params, cache, tokens, slot, offset, n_valid,
                      mesh, kv_bound, pages_row):
        """One prompt chunk of one request, written straight into a KV pool.

        tokens: (1, c) — a bucketed chunk padded past ``n_valid``; global
        positions are ``[offset, offset + c)``.  ``cache`` is the page
        pool's cache tree and ``pages_row`` the slot's page-table row, the
        pages the chunk writes through and reads back.  Returns the logits
        at the chunk's last *valid* position — the next-token logits once
        the final chunk lands — and the updated pool cache with the slot's
        index advanced to ``offset + n_valid``.  ``kv_bound`` is a STATIC
        upper bound (>= offset + c, power-of-two bucketed) on the KV
        prefix the chunk reads back, so short prompts do not pay
        max_len-sized attention.  Causality makes the result independent
        of the bucket padding, and the per-chunk computation is
        row-identical to one whole-prompt prefill, so a chunked ingest is
        token-identical to a blocking one.
        """
        b, c = tokens.shape
        positions = offset + jnp.broadcast_to(
            jnp.arange(c, dtype=jnp.int32), (b, c))
        x = self.embed_inputs(params, {"tokens": tokens}, mesh, positions)
        chunk_cache = {n: c for n, c in cache.items() if n in CARRIED}
        chunk_cache.update(offset=offset, n_valid=n_valid,
                           kv_bound=int(kv_bound), pages_row=pages_row)
        with jax.named_scope("layers"):
            x, stores = self.backbone(params, x, positions, mesh, "chunk",
                                      cache=chunk_cache)
        x_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = self.logits_from(params, x_last, mesh)
        index = cache["index"].at[slot].set(offset + n_valid)
        return logits, dict(stores, index=index)

    def decode_step(self, params, cache, tokens, mesh):
        b, s = tokens.shape
        idx = cache["index"]
        if jnp.ndim(idx) == 1:      # slot-wise: per-row lengths
            positions = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        else:
            positions = idx + jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s))
        with jax.named_scope("embed"):
            x = L.embed(params["embed"], tokens, self.cfg, mesh,
                        positions=positions)
        with jax.named_scope("layers"):
            x, new_cache = self.backbone(params, x, positions, mesh,
                                         "decode", cache=cache)
        logits = self.logits_from(params, x, mesh)
        return logits, new_cache


def model_for(cfg: ModelConfig, remat: str = "dots") -> BaseLM:
    from repro.models.moe import MoELM
    from repro.models.ssm import XLSTM
    from repro.models.mamba import ZambaHybrid
    from repro.models.encdec import EncDecLM
    from repro.models.vlm import VLM

    cls = {"dense": DenseLM, "moe": MoELM, "ssm_xlstm": XLSTM,
           "hybrid_mamba": ZambaHybrid, "encdec": EncDecLM, "vlm": VLM}[cfg.family]
    return cls(cfg, remat=remat)
