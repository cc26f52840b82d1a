"""Counts of required work and the peak table, pinned to hand arithmetic
at both configurations' published shapes."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import reference, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return reference.model_dims(
        json.loads((CONFIGS / f"{name}.json").read_text())["config"])


@pytest.mark.parametrize("name,kv,weights", [
    # 2 (K, V) x 30 layers x 32 heads x 128 x 2 bytes
    ("deepseek-7b", 491_520, 13_820_731_392),
    # 2 x 24 layers x 32 heads x 64 x 2 bytes
    ("stablelm-2-1.6b", 196_608, 3_289_030_656),
])
def test_kv_and_weight_bytes(name, kv, weights):
    m = dims(name)
    assert work.kv_bytes_per_token(m) == kv
    assert work.weight_bytes(m) == weights
    assert json.loads((CONFIGS / f"{name}.json").read_text())[
        "kv_bytes_per_token"] == kv


def test_decode_step_counts_by_hand():
    m = dims("deepseek-7b")
    live = [100, 300]
    flops, nbytes = work.decode_step(m, live)
    per_layer = 4096 * 4096 * 4 + 3 * 4096 * 11008
    assert flops == 2 * 2 * (30 * per_layer + 4096 * 102400) \
        + 4 * 30 * 32 * 128 * 400
    assert nbytes == 13_820_731_392 + 400 * 491_520 + 2 * 102400 * 2


def test_paged_attention_counts_need_not_grid():
    m = dims("stablelm-2-1.6b")
    flops, nbytes = work.paged_attention_call(m, [1000, 24])
    # q and out: 2 rows x 32 heads x 64 x 2 bytes, each; K and V of 1024
    # live tokens once: 2 x 1024 x 32 x 64 x 2 bytes
    assert nbytes == 2 * 2 * 32 * 64 * 2 + 2 * 1024 * 32 * 64 * 2
    assert flops == 4 * 32 * 64 * 1024
    # pages or passes do not enter: the same live tokens in other rows
    assert work.paged_attention_call(m, [512, 512])[0] == flops


def test_chunk_step_counts_by_hand():
    m = dims("stablelm-2-1.6b")
    flops, nbytes = work.chunk_step(m, 128, 256)
    ctx = 256 * 128 + 128 * 129 // 2
    assert flops == 128 * work.matmul_flops_per_token(m) \
        + 4 * 24 * 32 * 64 * ctx
    assert nbytes == 3_289_030_656 + 384 * 196_608 + 100352 * 2


def test_peaks_and_unknown_device():
    p = work.peaks_for("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.peaks_for("cpu")
    assert work.roofline_s(197e12, 1.0, p) == (1.0, "compute")
    assert work.roofline_s(1.0, 819e9, p) == (1.0, "memory")
