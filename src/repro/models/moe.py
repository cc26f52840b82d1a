"""Token-choice top-k Mixture-of-Experts (granite-moe, dbrx, DeepSeekMoE).

Serving (prefill, chunk and decode) routes **dropless** over the experts
this device holds (``held_experts_mlp``): the router scores all
``num_experts`` and picks its top k, and each held expert's output
enters a token's result weighted by its gate (zero where it was not
picked), so no token is dropped and a token's output does not depend on
the rest of the batch.  ``experts_held`` experts from ``expert_offset``
are held (all by default); with a share of them, what the experts held
elsewhere would add is left out here — one device's part of an expert-
parallel layer, without the exchange.  Each held expert is computed for
every token, which costs the held experts' FLOPs per token and reads
each held expert's weights once, as any step that routes to it must.
DeepSeekMoE adds always-on shared experts, softmax gates that are not
renormalised (``norm_topk``) and leading dense layers
(``first_dense_layers``, ``dense_blocks``).  Computing every held expert
for every token costs E_held/k times the routed FLOPs of a top-k pick
(5x for granite-moe, 4x for dbrx when all their experts are held), so a
long prompt runs through it in pieces of ``held_chunk`` tokens, whose
(tokens, experts, d_ff) intermediate is no larger than the capacity
dispatch's; a grouped matmul over each expert's picks is ROADMAP R5.

Training (mode 'full') keeps the capacity dispatch below.  Its dispatch is gather/scatter based (GShard capacity semantics, per-batch-row
groups) rather than one-hot-einsum based, so the dispatch tensors stay
O(tokens·k) instead of O(tokens·experts·capacity).  The MoE layer chunks
internally over the sequence axis so prefill at 32k tokens uses the same
bounded working set as a training microbatch.

Sharding: expert weights are (experts, embed, ff).  On a 16-way model axis
dbrx (16 experts) gets true expert parallelism; granite (40 experts) hits
the divisibility fallback and the rules engine automatically degrades to
TP-within-expert (ff=512 shards 16-way) — the fallback is recorded in the
EASEY tuning report.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.params import ParamDef
from repro.models.transformer import DenseLM, remat_wrap, stack_defs
from repro.sharding.rules import shard_constraint

_MOE_SEQ_CHUNK = 2048


def held(cfg) -> int:
    """Routed experts held here."""
    return cfg.experts_held or cfg.num_experts


def moe_defs(cfg) -> dict:
    E, m = cfg.num_experts, cfg.d_model
    f = cfg.moe_d_ff or cfg.d_ff
    Eh = held(cfg)
    d = {
        "router": ParamDef((m, E), ("embed", "experts")),
        "wi": ParamDef((Eh, m, f), ("experts", "embed", "mlp")),
        "wo": ParamDef((Eh, f, m), ("experts", "mlp", "embed")),
    }
    if cfg.activation in ("silu", "geglu"):
        d["wg"] = ParamDef((Eh, m, f), ("experts", "embed", "mlp"))
    if cfg.shared_experts:
        d["shared"] = L.mlp_defs(cfg.replace(d_ff=cfg.shared_experts * f))
    return d


def gates(router_logits: jax.Array, cfg):
    """(top-k gate weights, top-k expert ids) of float32 router logits:
    softmax over all experts, renormalised over the top k where
    ``norm_topk``."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk:
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    return top, idx


def expert_act(h, gate, cfg):
    """The experts' activation, one for serving and training: SwiGLU
    where they have a gate projection."""
    if gate is not None:
        return jax.nn.silu(gate) * h
    if cfg.activation == "gelu":
        return jax.nn.gelu(h)
    if cfg.activation == "sq_relu":
        return jnp.square(jax.nn.relu(h))
    return h


def held_chunk(cfg) -> int:
    """Tokens per piece of ``held_experts_mlp``: a power of two with
    tokens x held experts at most the capacity dispatch's routed rows
    per ``_MOE_SEQ_CHUNK`` tokens (k of them per token)."""
    n = max(_MOE_SEQ_CHUNK * cfg.experts_per_token // held(cfg), 1)
    return 1 << (n.bit_length() - 1)


def held_experts_mlp(p, x, cfg, valid=None):
    """Dropless routed part of the layer for x: (b, s, m), from the held
    experts, plus the shared experts.  Returns (y, counts): ``counts``
    (num_experts,) int32 is how many of the ``valid`` (b, s) tokens (all
    when None) the router sent to each expert, held or not.  A sequence
    longer than ``held_chunk`` runs in pieces of that many tokens."""
    b, s, m = x.shape
    n = held_chunk(cfg)
    if s <= n:
        return _held_experts(p, x, cfg, valid)
    pad = -s % n
    v = jnp.ones((b, s), bool) if valid is None else \
        jnp.broadcast_to(valid, (b, s))
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, n, m)
    vs = jnp.pad(v, ((0, 0), (0, pad))).reshape(b, -1, n)

    def body(counts, piece):
        y, c = _held_experts(p, piece[0], cfg, piece[1])
        return counts + c, y

    counts, ys = jax.lax.scan(body, jnp.zeros(cfg.num_experts, jnp.int32),
                              (xs.swapaxes(0, 1), vs.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).reshape(b, -1, m)[:, :s], counts


def _held_experts(p, x, cfg, valid):
    Eh, off = held(cfg), cfg.expert_offset
    with jax.named_scope("router"):
        logits = jnp.einsum("bsm,me->bse", x, p["router"],
                            preferred_element_type=jnp.float32)
        top, idx = gates(logits, cfg)
        picked = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.int32)
        if valid is not None:
            picked = picked * valid[..., None, None].astype(jnp.int32)
        counts = picked.sum((0, 1, 2))
        # the gate of each held expert per token: 0 where it was not
        # picked (ids outside [off, off + Eh) match no held expert)
        w = jnp.einsum("bsk,bske->bse", top,
                       jax.nn.one_hot(idx - off, Eh, dtype=jnp.float32))
    with jax.named_scope("experts"):
        h = jnp.einsum("bsm,emf->bsef", x, p["wi"])
        g = jnp.einsum("bsm,emf->bsef", x, p["wg"]) if "wg" in p else None
        h = expert_act(h, g, cfg) * w[..., None].astype(x.dtype)
        y = jnp.einsum("bsef,efm->bsm", h, p["wo"])
    if "shared" in p:
        with jax.named_scope("shared"):
            y = y + L.mlp(p["shared"], x, cfg, None)
    return y, counts


def route_tokens(router_logits: jax.Array, k: int, capacity: int,
                 norm_topk: bool = True):
    """router_logits: (b, s, E) fp32.  Returns (slot, gates, keep, aux_loss).

    slot: (b, s*k) int32 in [0, E*C]; E*C is the drop sentinel.
    Position-in-expert is assigned in token order per batch row (GShard).
    """
    b, s, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (b, s, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)          # renormalize
    flat_e = expert_idx.reshape(b, s * k)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (b, s*k, E)
    pos = jnp.cumsum(oh, axis=1) - oh                        # rank within expert
    pos_in_e = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]
    keep = pos_in_e < capacity
    slot = jnp.where(keep, flat_e * capacity + pos_in_e, E * capacity)

    # load-balance auxiliary loss (Switch style): E * sum_e f_e * P_e
    frac = oh.reshape(b, s, k, E).sum(2).mean(axis=(0, 1)).astype(jnp.float32) / k
    mean_p = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(frac * mean_p)
    return slot, gate_vals.astype(jnp.float32), keep, aux


def moe_mlp_chunk(p, x, cfg, mesh):
    """x: (b, S, m) one seq chunk. Returns (y, aux)."""
    b, S, m = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = max(int(cfg.capacity_factor * k * S / E), 1)
    C = -(-C // 8) * 8  # round up to 8 for tiling friendliness

    logits = jnp.einsum("bsm,me->bse", x, p["router"],
                        preferred_element_type=jnp.float32)
    slot, gate_w, keep, aux = route_tokens(logits, k, C, cfg.norm_topk)

    # slot -> token scatter (int indices only), then row gather.
    tok_ids = jnp.broadcast_to(
        (jnp.arange(S * k, dtype=jnp.int32) // k)[None], (b, S * k))
    batch_ix = jnp.broadcast_to(jnp.arange(b)[:, None], (b, S * k))
    slot_tok = jnp.full((b, E * C + 1), S, jnp.int32)        # default: pad row
    slot_tok = slot_tok.at[batch_ix, slot].set(tok_ids, mode="drop")
    slot_tok = slot_tok[:, : E * C]

    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, m), x.dtype)], axis=1)
    ex = jnp.take_along_axis(x_pad, slot_tok[..., None], axis=1)
    ex = ex.reshape(b, E, C, m)
    ex = shard_constraint(ex, ("act_batch", "act_experts", None, None), mesh)

    h = jnp.einsum("becm,emf->becf", ex, p["wi"])
    g = jnp.einsum("becm,emf->becf", ex, p["wg"]) if "wg" in p else None
    h = expert_act(h, g, cfg)
    h = shard_constraint(h, ("act_batch", "act_experts", None, "act_mlp"), mesh)
    ye = jnp.einsum("becf,efm->becm", h, p["wo"])
    ye = shard_constraint(ye, ("act_batch", "act_experts", None, None), mesh)

    ye_flat = ye.reshape(b, E * C, m)
    ye_pad = jnp.concatenate([ye_flat, jnp.zeros((b, 1, m), ye.dtype)], axis=1)
    y_assign = jnp.take_along_axis(ye_pad, slot[..., None], axis=1)  # (b, s*k, m)
    w = gate_w * keep.astype(jnp.float32).reshape(b, S, k)
    y = jnp.einsum("bskm,bsk->bsm", y_assign.reshape(b, S, k, m),
                   w.astype(y_assign.dtype))
    y = shard_constraint(y, ("act_batch", "act_seq", "act_embed"), mesh)
    if "shared" in p:
        y = y + L.mlp(p["shared"], x, cfg, mesh)
    return y, aux


def moe_mlp(p, x, cfg, mesh):
    """Chunked over sequence; returns (y, mean aux loss)."""
    b, s, m = x.shape
    chunk = min(_MOE_SEQ_CHUNK, s)
    if s <= chunk:
        return moe_mlp_chunk(p, x, cfg, mesh)
    assert s % chunk == 0
    n = s // chunk
    xc = x.reshape(b, n, chunk, m).transpose(1, 0, 2, 3)

    def body(_, xi):
        y, aux = moe_mlp_chunk(p, xi, cfg, mesh)
        return None, (y, aux)

    _, (yc, auxc) = jax.lax.scan(body, None, xc)
    y = yc.transpose(1, 0, 2, 3).reshape(b, s, m)
    return y, auxc.mean()


class MoELM(DenseLM):
    """Attention + MoE FFN, after ``first_dense_layers`` dense blocks
    (their own scanned stack, ``dense_blocks``).  Training threads the
    router's aux loss through the layer scan; serving routes dropless
    (``held_experts_mlp``) and, where the cache carries ``route_counts``
    (the paged pool's), adds each step's routing counts to it on the
    device."""

    def mlp_defs(self) -> dict:
        return moe_defs(self.cfg)

    def param_table(self) -> dict:
        cfg = self.cfg
        n0 = cfg.first_dense_layers
        table = {"embed": L.embed_defs(cfg),
                 "blocks": stack_defs(self.block_defs(),
                                      cfg.num_layers - n0),
                 "ln_f": L.norm_defs(cfg.d_model, cfg.norm)}
        if n0:
            table["dense_blocks"] = stack_defs(
                self.block_defs(L.mlp_defs(cfg)), n0)
        return table

    def layer_stacks(self, params) -> list:
        n0 = self.cfg.first_dense_layers
        dense = [(params["dense_blocks"], 0, super().block_apply)] \
            if n0 else []
        return dense + [(params["blocks"], n0, self.block_apply)]

    def moe_block(self, p, x, mesh, positions, mode, cache):
        """(x, new cache, aux loss or None)."""
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        attn_out, new_cache = self.attend(p["attn"], h, mesh, positions,
                                          mode, cache)
        x = x + attn_out
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        with jax.named_scope("moe"):
            if mode == "full":
                y, aux = moe_mlp(p["mlp"], h, cfg, mesh)
                return x + y, new_cache, aux
            y, counts = held_experts_mlp(p["mlp"], h, cfg,
                                         _valid_tokens(cache, h.shape[1]))
        if cache is not None and "route_counts" in cache:
            new_cache = dict(new_cache,
                             route_counts=cache["route_counts"] + counts)
        return x + y, new_cache, None

    def block_apply(self, p, x, mesh, positions, mode, cache):
        x, new_cache, _ = self.moe_block(p, x, mesh, positions, mode, cache)
        return x, new_cache

    def backbone(self, params, x, positions, mesh, mode, cache=None):
        if mode != "full":
            return super().backbone(params, x, positions, mesh, mode, cache)
        cfg = self.cfg
        if held(cfg) != cfg.num_experts:
            raise ValueError("training needs every routed expert held")
        if cfg.first_dense_layers:
            dense = remat_wrap(lambda bp, y: super(MoELM, self).block_apply(
                bp, y, mesh, positions, "full", None)[0], self.remat)
            x, _ = jax.lax.scan(lambda y, bp: (dense(bp, y), None), x,
                                params["dense_blocks"])
        fn = remat_wrap(lambda bp, y: self.moe_block(
            bp, y, mesh, positions, "full", None)[::2], self.remat)

        def body(carry, bp):
            y, aux_sum = carry
            y, aux = fn(bp, y)
            return (y, aux_sum + aux), None

        (x, aux_sum), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                       params["blocks"])
        self._last_aux = aux_sum / (cfg.num_layers - cfg.first_dense_layers)
        return x, None

    def loss(self, params, batch, mesh):
        loss, metrics = super().loss(params, batch, mesh)
        aux = getattr(self, "_last_aux", 0.0)
        total = loss + self.cfg.router_aux_coef * aux
        metrics = dict(metrics, aux_loss=aux, loss=total)
        return total, metrics


def _valid_tokens(cache, s):
    """Which of a step's (b, s) tokens are real, for the routing counts:
    a decode row whose page-table row is zeroed (a free slot, or one
    mid-prefill) is not, nor a chunk's bucket padding; None: all."""
    if cache is None:
        return None
    if "pages" in cache:
        return cache["pages"][:, :1] != 0
    if "n_valid" in cache:
        return (jnp.arange(s) < cache["n_valid"])[None]
    return None
