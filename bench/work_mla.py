"""Operations and bytes a DeepSeek-V2 call needs (latent attention and
DeepSeekMoE), from the shapes of ``reference_mla.model_dims`` and the
live lengths, as ``bench/work.py`` counts them for the dense decoders.

The counts are of the work the call requires, never of what a kernel
happens to do:

* a latent row is its published ``kv_lora_rank + qk_rope_head_dim``
  values, 2 bytes each, read once per layer — not the lanes the pool pads
  it to, nor once per head;
* attention is the absorbed form the program runs: per head and live
  token, ``rank + rope`` multiply-adds for the score and ``rank`` for the
  value sum; the absorption itself (``q_nope`` into the rank, the rank
  into ``v``) is a per-token matmul;
* the routed experts are those the router sends a token to among the
  held ones: ``top_k * held / experts`` of them per token on average;
  every held expert's weights are read once per step, as a step that
  routes one token to each must.
"""

from __future__ import annotations

from bench.work import ACT_BYTES, roofline_s  # noqa: F401


def attention_params(m: dict) -> int:
    d, H, r = m["d"], m["heads"], m["rank"]
    n, e, v = m["nope"], m["rope"], m["v"]
    return d * H * (n + e) + d * (r + e) + r * H * (n + v) + H * v * d


def expert_params(m: dict) -> int:
    return 3 * m["d"] * m["moe_d_ff"]


def weight_bytes(m: dict) -> int:
    """Every weight held once: embeddings, norms, both kinds of layer."""
    d = m["d"]
    attn = attention_params(m) + m["rank"] + 2 * d
    dense = attn + 3 * d * m["d_ff"]
    moe = attn + d * m["experts"] + (m["held"] + m["shared"]) \
        * expert_params(m)
    n0 = m["dense_layers"]
    return (n0 * dense + (m["layers"] - n0) * moe + 2 * m["vocab"] * d
            + d) * ACT_BYTES


def latent_bytes_per_token(m: dict) -> int:
    """One token's latent rows over all layers, as published."""
    return m["layers"] * (m["rank"] + m["rope"]) * ACT_BYTES


def matmul_flops_per_token(m: dict) -> int:
    """Weight matmuls of one token through the model (the absorbed
    attention's projections, the router, the shared experts and the
    routed experts it reaches here), unembedding included."""
    H, r, n, v = m["heads"], m["rank"], m["nope"], m["v"]
    attn = attention_params(m) + H * n * r + H * r * v - r * H * (n + v)
    routed = m["top_k"] * m["held"] / m["experts"]
    moe = attn + m["d"] * m["experts"] + (m["shared"] + routed) \
        * expert_params(m)
    n0 = m["dense_layers"]
    per = n0 * (attn + 3 * m["d"] * m["d_ff"]) + (m["layers"] - n0) * moe
    return int(2 * (per + m["d"] * m["vocab"]))


def attention_flops(m: dict, kv_tokens: int) -> int:
    """Scores and value sums of one query per head over `kv_tokens` live
    latent rows, all layers."""
    return 2 * m["layers"] * m["heads"] * (2 * m["rank"] + m["rope"]) \
        * kv_tokens


def decode_step(m: dict, live: list) -> tuple:
    """(flops, bytes) a decode step needs for active rows at live lengths
    `live` (each counting the token the step writes): the weights once,
    the live latent rows once, and the logits out."""
    rows, kv = len(live), int(sum(live))
    flops = rows * matmul_flops_per_token(m) + \
        attention_flops(m, kv)
    nbytes = weight_bytes(m) + kv * latent_bytes_per_token(m) + \
        rows * m["vocab"] * ACT_BYTES
    return flops, nbytes


def mla_attention_call(m: dict, live: list) -> tuple:
    """(flops, bytes) of one latent-attention decode call (one layer): the
    absorbed queries in, each live latent row once, the rank-wide head
    outputs out."""
    H, r, e = m["heads"], m["rank"], m["rope"]
    rows, kv = len(live), int(sum(live))
    flops = 2 * H * (2 * r + e) * kv
    nbytes = (rows * H * (r + e) + kv * (r + e) + rows * H * r) * ACT_BYTES
    return flops, nbytes
