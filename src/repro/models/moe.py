"""Token-choice top-k Mixture-of-Experts (granite-moe, dbrx).

Dispatch is gather/scatter based (GShard capacity semantics, per-batch-row
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
from repro.models.transformer import DenseLM
from repro.sharding.rules import shard_constraint

_MOE_SEQ_CHUNK = 2048


def moe_defs(cfg) -> dict:
    E, m, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    d = {
        "router": ParamDef((m, E), ("embed", "experts")),
        "wi": ParamDef((E, m, f), ("experts", "embed", "mlp")),
        "wo": ParamDef((E, f, m), ("experts", "mlp", "embed")),
    }
    if cfg.activation in ("silu", "geglu"):
        d["wg"] = ParamDef((E, m, f), ("experts", "embed", "mlp"))
    return d


def route_tokens(router_logits: jax.Array, k: int, capacity: int):
    """router_logits: (b, s, E) fp32.  Returns (slot, gates, keep, aux_loss).

    slot: (b, s*k) int32 in [0, E*C]; E*C is the drop sentinel.
    Position-in-expert is assigned in token order per batch row (GShard).
    """
    b, s, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (b, s, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)              # renormalize
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
    slot, gates, keep, aux = route_tokens(logits, k, C)

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
    if "wg" in p:
        h = jax.nn.silu(jnp.einsum("becm,emf->becf", ex, p["wg"])) * h
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.activation == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    h = shard_constraint(h, ("act_batch", "act_experts", None, "act_mlp"), mesh)
    ye = jnp.einsum("becf,efm->becm", h, p["wo"])
    ye = shard_constraint(ye, ("act_batch", "act_experts", None, None), mesh)

    ye_flat = ye.reshape(b, E * C, m)
    ye_pad = jnp.concatenate([ye_flat, jnp.zeros((b, 1, m), ye.dtype)], axis=1)
    y_assign = jnp.take_along_axis(ye_pad, slot[..., None], axis=1)  # (b, s*k, m)
    w = gates * keep.astype(jnp.float32).reshape(b, S, k)
    y = jnp.einsum("bskm,bsk->bsm", y_assign.reshape(b, S, k, m),
                   w.astype(y_assign.dtype))
    y = shard_constraint(y, ("act_batch", "act_seq", "act_embed"), mesh)
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
    """Dense attention + MoE FFN. Aux loss threaded through the layer scan."""

    def mlp_defs(self) -> dict:
        return moe_defs(self.cfg)

    def block_apply(self, p, x, mesh, positions, mode, cache):
        cfg = self.cfg
        h = L.apply_norm(p["ln1"], x, cfg.norm)
        attn_out, new_cache = L.attention(
            p["attn"], h, cfg, mesh, positions=positions, mode=mode,
            cache=cache, window=cfg.window or None)
        x = x + attn_out
        h = L.apply_norm(p["ln2"], x, cfg.norm)
        y, aux = moe_mlp(p["mlp"], h, cfg, mesh)
        return x + y, (new_cache, aux)

    def block_cache(self, out):
        return out[0]          # (new_cache, aux): decode drops the aux

    # backbone: thread aux through the scan carry
    def backbone(self, params, x, positions, mesh, mode, cache=None):
        blocks = params["blocks"]
        if mode == "full":
            def raw(bp, y):
                out, (_, aux) = self.block_apply(bp, y, mesh, positions, "full", None)
                return out, aux
            fn = jax.checkpoint(
                raw, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable) \
                if self.remat == "dots" else (jax.checkpoint(raw) if self.remat == "full" else raw)

            def body(carry, bp):
                y, aux_sum = carry
                y, aux = fn(bp, y)
                return (y, aux_sum + aux), None

            (x, aux_sum), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), blocks)
            self._last_aux = aux_sum / self.cfg.num_layers
            return x, None

        if mode in ("decode", "chunk"):
            return super().backbone(params, x, positions, mesh, mode, cache)

        def body_p(carry, bp):
            y, (nc, _) = self.block_apply(bp, carry, mesh, positions, "prefill", None)
            return y, (nc["k"], nc["v"])

        x, kvs = jax.lax.scan(body_p, x, blocks)
        return x, {"k": kvs[0], "v": kvs[1],
                   "index": jnp.asarray(x.shape[1], jnp.int32)}

    def loss(self, params, batch, mesh):
        loss, metrics = super().loss(params, batch, mesh)
        aux = getattr(self, "_last_aux", 0.0)
        total = loss + self.cfg.router_aux_coef * aux
        metrics = dict(metrics, aux_loss=aux, loss=total)
        return total, metrics
