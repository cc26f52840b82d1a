"""Operations and bytes that a call needs, from the configuration's
shapes and the live lengths, and the table of peaks.

The counts are of the work the call requires, never of what a kernel
happens to do: the live K and V are read once whatever the kernel's
passes or grid, and a decode step computes only its active rows.
Bytes are of the served dtype (bfloat16, 2 bytes).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
ACT_BYTES = 2


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of `device_kind`; an unknown device is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} has no peaks in "
                       f"{path.name} (known: {sorted(table)})")
    return table[device_kind]


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one token over all layers."""
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * ACT_BYTES


def layer_matmul_params(m: dict) -> int:
    d, H, K, dh, f = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["d_ff"]
    return d * H * dh + 2 * d * K * dh + H * dh * d + 3 * d * f


def weight_bytes(m: dict) -> int:
    """Every weight once: the layers, both embeddings, norms and biases."""
    d, H, K, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    per_layer = layer_matmul_params(m) + (4 * d if m["norm"] == "layernorm"
                                          else 2 * d)
    if m["qkv_bias"]:
        per_layer += H * dh + 2 * K * dh
    final = 2 * d if m["norm"] == "layernorm" else d
    return (m["layers"] * per_layer + 2 * m["vocab"] * d + final) * ACT_BYTES


def matmul_flops_per_token(m: dict) -> int:
    """Weight matmuls of one token through the model, unembedding
    included (the embedding is a gather)."""
    return 2 * (m["layers"] * layer_matmul_params(m) + m["d"] * m["vocab"])


def attention_flops(m: dict, q_tokens: int, kv_tokens: int) -> int:
    """QK^T and PV of `q_tokens` queries against `kv_tokens` keys each,
    all layers."""
    return 4 * m["layers"] * m["heads"] * m["head_dim"] * q_tokens * kv_tokens


def decode_step(m: dict, live: list) -> tuple:
    """(flops, bytes) a decode step needs for active rows at KV lengths
    `live` (each counting the token the step writes): the weights once,
    the live K and V once, the new K and V, and the logits out."""
    rows, kv = len(live), int(sum(live))
    flops = rows * matmul_flops_per_token(m) + \
        4 * m["layers"] * m["heads"] * m["head_dim"] * kv
    nbytes = weight_bytes(m) + kv * kv_bytes_per_token(m) + \
        rows * m["vocab"] * ACT_BYTES
    return flops, nbytes


def chunk_step(m: dict, tokens: int, offset: int) -> tuple:
    """(flops, bytes) of one prefill chunk of `tokens` at `offset`: the
    chunk's matmuls and causal attention over its prefix, the weights
    once, the prefix's K and V once, the chunk's K and V written."""
    ctx = offset * tokens + tokens * (tokens + 1) // 2
    flops = tokens * matmul_flops_per_token(m) + \
        4 * m["layers"] * m["heads"] * m["head_dim"] * ctx
    nbytes = weight_bytes(m) + (offset + tokens) * kv_bytes_per_token(m) \
        + m["vocab"] * ACT_BYTES
    return flops, nbytes


def paged_attention_call(m: dict, live: list) -> tuple:
    """(flops, bytes) of one paged-attention call (one layer): q in, the
    live K and V once, and the output."""
    H, K, dh = m["heads"], m["kv_heads"], m["head_dim"]
    rows, kv = len(live), int(sum(live))
    flops = 4 * H * dh * kv
    nbytes = 2 * rows * H * dh * ACT_BYTES + 2 * kv * K * dh * ACT_BYTES
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time, bound) on a chip with `peaks`."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
