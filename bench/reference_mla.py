"""Plain float32 reference forward of DeepSeek-V2 (latent attention and
DeepSeekMoE), for the cells that serve it, with the comparison of
``bench/reference.py``.

It imports nothing of the program.  Weights come from
``bench/weights_mla.py``.  The forward follows the published model
(DeepSeek-V2, arXiv:2405.04434, and its reference code) in ``jax.numpy``
under ``default_matmul_precision("highest")``, with no kernel, cache or
batching: one sequence at a time, layer by layer, each layer's weights
upcast to float32 only while it runs.

* Attention in its published, unabsorbed form: ``q = h wq`` split into
  ``q_nope`` and ``q_pe``; ``[c_kv, k_pe] = h wkv_a``; ``c_kv`` RMS-
  normalised (``kv_a_layernorm``); ``[k_nope, v] = c_kv wkv_b`` per head;
  ``q_pe`` and the one-head ``k_pe`` rotated; scores ``[q_nope, q_pe] .
  [k_nope, k_pe]`` times ``softmax_scale``; causal softmax; ``o wo``.
* YaRN rope: the original and the ÷factor frequencies blended along a
  linear ramp between the correction dims of ``beta_fast`` and
  ``beta_slow`` rotations at ``original_max_position_embeddings``; cos
  and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``; ``softmax_scale = (nope + rope) ** -0.5 *
  mscale(factor, mscale_all_dim) ** 2``.  Rotated pairs are adjacent dims
  (2i, 2i+1): the published code regroups q_pe and k_pe as (dim/2, 2)
  before ``rotate_half``, the same pairing.
* Layers ``< first_k_dense_replace``: SwiGLU of ``intermediate_size``.
  The rest: DeepSeekMoE — router logits in float32 over all
  ``n_routed_experts_published`` experts, softmax, top
  ``num_experts_per_tok``, gates not renormalised (``norm_topk_prob``
  false) times ``routed_scaling_factor``; the held experts ``[offset,
  offset + n_routed_experts)`` each add their SwiGLU output (width
  ``moe_intermediate_size``) times their gate where picked; the
  ``n_shared_experts`` shared experts, one SwiGLU of their summed width,
  always add theirs.  The experts held on other chips add nothing here,
  in the reference as in the program (the configuration's cut).

``precision="fp8"`` is the control of ``bench/reference.py``: every
matmul input, weights and activations, the router's too, rounded to
float8 e4m3 with one scale per tensor.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (PRECISIONS, Q_BLOCK, _embed, _head, _mm,  # noqa: F401
                             bucket, readings, served_gaps, teacher_rows)


def model_dims(cfg: dict) -> dict:
    """The reference's view of a configuration file (the published keys at
    its top level, ``n_routed_experts`` being the experts held and
    ``expert_share`` where they start and how many the router scores)."""
    y = cfg.get("rope_scaling") or {}
    share = cfg["expert_share"]
    return {
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "rank": int(cfg["kv_lora_rank"]), "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "d_ff": int(cfg["intermediate_size"]),
        "moe_d_ff": int(cfg["moe_intermediate_size"]),
        "experts": int(share["n_routed_experts_published"]),
        "held": int(cfg["n_routed_experts"]),
        "offset": int(share["expert_offset"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "layers": int(cfg["num_hidden_layers"]),
        "vocab": int(cfg["vocab_size"]), "norm": "rmsnorm",
        "eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
        "yarn_factor": float(y.get("factor", 1.0)),
        "yarn_original": int(y.get("original_max_position_embeddings", 0)),
        "yarn_beta_fast": float(y.get("beta_fast", 32)),
        "yarn_beta_slow": float(y.get("beta_slow", 1)),
        "yarn_mscale": float(y.get("mscale", 1.0)),
        "yarn_mscale_all_dim": float(y.get("mscale_all_dim", 0.0)),
    }


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(m: dict) -> np.ndarray:
    dim, base = m["rope"], m["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if m["yarn_factor"] <= 1:
        return extra

    def corr(rot):
        return dim * math.log(m["yarn_original"] / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(m["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return extra / m["yarn_factor"] * (1 - mask) + extra * mask


def softmax_scale(m: dict) -> float:
    scale = (m["nope"] + m["rope"]) ** -0.5
    if m["yarn_mscale_all_dim"]:
        scale *= _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2
    return scale


def _rope(x, m):
    """x: (s, heads, rope), positions 0..s-1, adjacent pairs rotated."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * \
        jnp.asarray(inv_freq(m), jnp.float32)[None, :]
    att = _mscale(m["yarn_factor"], m["yarn_mscale"]) / \
        _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"])
    cos = (jnp.cos(ang) * att)[:, None, :]
    sin = (jnp.sin(ang) * att)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _rms(x, scale, m):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"]) \
        * scale


def _swiglu(h, p, precision):
    u = jax.nn.silu(_mm(h, p["wg"], precision, "se,ef->sf")) \
        * _mm(h, p["wi"], precision, "se,ef->sf")
    return _mm(u, p["wo"], precision, "sf,fe->se")


def _attention(h, a, m, precision):
    s, r, n = h.shape[0], m["rank"], m["nope"]
    q = _mm(h, a["wq"], precision, "se,ehd->shd")
    kv = _mm(h, a["wkv_a"], precision, "se,ec->sc")
    c = _rms(kv[:, :r], a["kv_norm"]["scale"], m)
    k_pe = _rope(kv[:, None, r:], m)                       # (s, 1, rope)
    kvb = _mm(c, a["wkv_b"], precision, "sr,rhd->shd")
    k = jnp.concatenate([kvb[..., :n], jnp.broadcast_to(
        k_pe, (s, m["heads"], m["rope"]))], -1)
    v = kvb[..., n:]
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], m)], -1)
    scale = softmax_scale(m)
    blocks = max(s // Q_BLOCK, 1)
    qb = q.reshape(blocks, s // blocks, *q.shape[1:])

    def attend(args):
        i, qi = args
        rows = i * qi.shape[0] + jnp.arange(qi.shape[0])
        sc = _mm(qi, k, precision, "qhd,thd->hqt") * scale
        sc = jnp.where(jnp.arange(s)[None, None, :] <= rows[None, :, None],
                       sc, -jnp.inf)
        return _mm(jax.nn.softmax(sc, -1), v, precision, "hqt,thd->qhd")

    o = jax.lax.map(attend, (jnp.arange(blocks), qb)).reshape(
        s, m["heads"], m["v"])
    return _mm(o, a["wo"], precision, "shd,hde->se")


def _moe(h, p, m, precision):
    logits = _mm(h, p["router"], precision, "se,ex->sx")
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    top = top * m["routed_scale"]
    y = _swiglu(h, p["shared"], precision)
    for j in range(m["held"]):
        gate = jnp.sum(jnp.where(idx == m["offset"] + j, top, 0.0), -1)
        expert = {w: p[w][j] for w in ("wi", "wg", "wo")}
        y = y + gate[:, None] * _swiglu(h, expert, precision)
    return y


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _block(blocks, layer, x, frozen, precision, moe):
    """Layer `layer` of the stacked `blocks` over one sequence x: (s, d)
    float32; `moe` says whether its MLP is DeepSeekMoE."""
    m = dict(frozen)
    p = jax.tree.map(lambda w: w[layer].astype(jnp.float32), blocks)
    x = x + _attention(_rms(x, p["ln1"]["scale"], m), p["attn"], m,
                       precision)
    h = _rms(x, p["ln2"]["scale"], m)
    return x + (_moe(h, p["mlp"], m, precision) if moe
                else _swiglu(h, p["mlp"], precision))


def hidden(weights, m: dict, seqs: list, precision: str = "f32") -> list:
    """Final hidden states ``(bucket, d)`` of each token sequence, padded
    to its bucket, layer by layer over every sequence."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    frozen = tuple(sorted(m.items()))
    with jax.default_matmul_precision("highest"):
        xs = []
        for toks in seqs:
            padded = np.zeros((bucket(len(toks)),), np.int32)
            padded[:len(toks)] = toks
            xs.append(_embed(weights["embed"]["embedding"],
                             jnp.asarray(padded)))
        stacks = [("dense_blocks", m["dense_layers"], False),
                  ("blocks", m["layers"] - m["dense_layers"], True)]
        for name, n, moe in stacks:
            for layer in range(n):
                i = jnp.int32(layer)
                xs = [_block(weights[name], i, x, frozen, precision, moe)
                      for x in xs]
    return xs


def logits_at(weights, m: dict, seqs: list, rows: list,
              precision: str = "f32") -> list:
    """Reference logits, float32 numpy ``(len(rows[i]), V)``, of each
    sequence ``seqs[i]`` at positions ``rows[i]``."""
    frozen = tuple(sorted(m.items()))
    out = []
    with jax.default_matmul_precision("highest"):
        for x, r in zip(hidden(weights, m, seqs, precision), rows):
            idx = np.zeros((bucket(len(r)),), np.int32)
            idx[:len(r)] = r
            lg = _head(weights["ln_f"], weights["embed"]["unembed"],
                       x[jnp.asarray(idx)], frozen, precision)
            out.append(np.asarray(lg)[:len(r)])
    return out


def control_picks(weights, m: dict, seqs: list, rows: list) -> tuple:
    """The float8 control in the program's place: per sequence, its greedy
    picks at ``rows`` and the logits they were made from."""
    low = logits_at(weights, m, seqs, rows, "fp8")
    return ([lg.argmax(-1) for lg in low], [lg.max(-1) for lg in low])
