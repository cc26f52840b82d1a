"""Latent attention (MLA) and DeepSeekMoE on the serving path, at smoke
size on the CPU: YaRN, the latent decode kernel against its gather
oracle, the expert shares, dropless routing, sizing, the engine and pool,
and the routing counters."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.tuning import (active_param_count, kv_bytes_per_token,
                               param_count_estimate)
from repro.kernels.mla_attention import mla_decode_attention_pallas
from repro.models import mla, moe
from repro.models.params import init_params
from repro.models.transformer import model_for
from repro.serving.engine import ServeEngine
from repro.serving.pool import KVCachePool, PagedKVCachePool
from repro.serving.trace import zipf_trace

SMOKE = "deepseek-v2-lite-smoke"


# ---------------------------------------------------------------------------
# YaRN, by hand


def test_yarn_frequencies_and_mscale_by_hand():
    cfg = get_config("deepseek-v2-lite")
    # mscale = 0.1 * 0.707 * ln 40 + 1; softmax scale 192^-0.5 * mscale^2
    ms = 0.1 * 0.707 * math.log(40) + 1
    assert mla.yarn_mscale(40, 0.707) == pytest.approx(1.2608042, abs=1e-6)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * ms * ms)
    # correction dims at 4096 original positions, 64 rope dims, base 1e4:
    # 32 rotations -> 10.47 (floor 10), 1 rotation -> 22.54 (ceil 23)
    f = mla.rope_freqs(cfg)
    assert f.shape == (32,) and f.dtype == np.float32
    orig = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], orig[:11], rtol=1e-6)  # ramp 0
    np.testing.assert_allclose(f[23:], orig[23:] / 40, rtol=1e-6)  # ramp 1
    ramp = (16 - 10) / 13                       # index 16: 6/13 interpolated
    np.testing.assert_allclose(
        f[16], orig[16] / 40 * ramp + orig[16] * (1 - ramp), rtol=1e-6)
    # without yarn: plain RoPE; equal mscales leave the rotation unscaled
    plain = mla.rope_freqs(cfg.replace(rope_yarn=None))
    np.testing.assert_allclose(plain, orig, rtol=1e-6)
    x = jnp.ones((1, 3, 2, 64), jnp.float32)
    pos = jnp.arange(3)[None]
    r = mla._rope(x, pos, cfg)
    np.testing.assert_allclose(jnp.linalg.norm(r, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


# ---------------------------------------------------------------------------
# The latent decode kernel against its gather oracle


def _oracle(q, pool, pt, lens, layer, scale, vl):
    """Gather each slot's live rows through the page table, softmax in
    float32, weighted sum of the value lanes."""
    q, pool = np.asarray(q, np.float32), np.asarray(pool, np.float32)
    slots, H, _ = q.shape
    ps = pool.shape[2]
    out = np.zeros((slots, H, vl), np.float32)
    for s in range(slots):
        n = int(lens[s])
        if n == 0:
            continue
        rows = np.concatenate([pool[layer, pt[s, i]]
                               for i in range(-(-n // ps))])[:n]
        sc = q[s] @ rows.T * scale
        p = np.exp(sc - sc.max(1, keepdims=True))
        out[s] = (p / p.sum(1, keepdims=True)) @ rows[:, :vl]
    return out


@pytest.mark.parametrize("ps,mp,ppb,lens", [
    (4, 8, 3, (0, 5, 32, 1, 13)),
    (8, 4, 2, (32, 0, 0, 9)),
    (16, 3, 16, (48, 17, 0)),
    (2, 12, 5, (0, 0, 0)),
], ids=["ps4", "ps8-empty", "ps16-one-block", "all-empty"])
def test_mla_kernel_matches_gather_oracle(ps, mp, ppb, lens):
    rng = np.random.default_rng(ps * 100 + mp)
    L, lanes, H, vl = 2, 256, 4, 128
    slots = len(lens)
    P = slots * mp + 1
    pool = jnp.asarray(rng.normal(size=(L, P, ps, lanes)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(slots, H, lanes)), jnp.bfloat16)
    lens = np.asarray(lens, np.int32)
    pt = np.zeros((slots, mp), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for s in range(slots):
        for i in range(-(-lens[s] // ps)):
            pt[s, i] = free.pop()
    got = mla_decode_attention_pallas(
        q, pool, jnp.asarray(pt), jnp.asarray(lens), 1, scale=0.1,
        value_lanes=vl, pages_per_block=ppb, interpret=True)
    want = _oracle(q, pool, pt, lens, 1, 0.1, vl)
    # the kernel rounds the probabilities to bfloat16 before the value
    # sum, as the gather path's softmax does; the oracle keeps float32
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=2e-2, rtol=2e-2)
    assert not np.asarray(got[lens == 0], np.float32).any()


# ---------------------------------------------------------------------------
# The expert layer: shares, dropless routing


def _moe_params(cfg, seed=0):
    table = moe.moe_defs(cfg)
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        init_params(table, jax.random.PRNGKey(seed)))


def test_expert_shares_sum_to_the_uncut_layer():
    """8 shares of the routed experts, each computed by a layer told which
    experts it holds; their outputs, the shared experts counted once,
    add up to the whole layer's."""
    full = smoke_config(SMOKE).replace(num_experts=16, experts_per_token=6)
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, full.d_model))
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.held_experts_mlp(p, x, full)
        shared = moe.L.mlp(p["shared"], x, full, None)
        parts = []
        for i in range(8):
            cut = full.replace(experts_held=2, expert_offset=2 * i)
            q = dict(p, **{w: p[w][2 * i:2 * i + 2]
                           for w in ("wi", "wg", "wo")})
            y, c = moe.held_experts_mlp(q, x, cut)
            np.testing.assert_array_equal(c, counts)
            parts.append(y - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5,
                               rtol=1e-5)
    assert int(counts.sum()) == 2 * 5 * 6


def test_routing_is_dropless_and_batch_independent():
    """A token's output alone equals its output among many tokens, even
    when all of them pick the same experts (a capacity layer drops)."""
    cfg = smoke_config(SMOKE).replace(experts_held=4, expert_offset=2)
    p = _moe_params(cfg, 3)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.d_model))
    x = x.at[0, 1:].set(x[0, 0] + 0.01 * x[0, 1:])   # crowd the same experts
    with jax.default_matmul_precision("highest"):
        batch, _ = moe.held_experts_mlp(p, x, cfg)
        for t in (0, 17, 63):
            alone, _ = moe.held_experts_mlp(p, x[:, t:t + 1], cfg)
            np.testing.assert_allclose(alone[0, 0], batch[0, t], atol=1e-5,
                                       rtol=1e-5)


def test_long_sequences_run_in_pieces(monkeypatch):
    """Past ``held_chunk`` tokens the layer runs in pieces (the last one
    padded), with the same output and counts as in one go."""
    cfg = smoke_config(SMOKE).replace(experts_held=4, expert_offset=2)
    p = _moe_params(cfg, 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 11, cfg.d_model))
    valid = (jnp.arange(11) < 9)[None]
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.held_experts_mlp(p, x, cfg, valid)
        monkeypatch.setattr(moe, "_MOE_SEQ_CHUNK", 8)
        assert moe.held_chunk(cfg) == 4          # 8 x 3 // 4 = 6 -> 4
        pieces, piece_counts = moe.held_experts_mlp(p, x, cfg, valid)
    np.testing.assert_allclose(pieces, whole, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(piece_counts, counts)
    assert int(counts.sum()) == 2 * 9 * 3


def test_gates_are_not_renormalised_without_norm_topk():
    cfg = smoke_config(SMOKE)
    logits = jnp.asarray([[[2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0, -4.0]]])
    top, idx = moe.gates(logits, cfg)
    probs = jax.nn.softmax(logits, -1)
    np.testing.assert_allclose(top[0, 0], probs[0, 0, :3], rtol=1e-6)
    np.testing.assert_array_equal(idx[0, 0], [0, 1, 2])
    top_n, _ = moe.gates(logits, cfg.replace(norm_topk=True))
    assert float(top_n.sum()) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Sizing, the engine and the pool


def test_sizing_counts_the_latent_row_and_the_held_experts():
    cfg = get_config("deepseek-v2-lite")
    assert kv_bytes_per_token(cfg) == 31104          # 27 x 576 x 2
    assert kv_bytes_per_token(cfg, stored=True) == 27 * 640 * 2
    held = cfg.replace(experts_held=8)
    assert param_count_estimate(cfg) == 15_706_484_224
    assert param_count_estimate(held) == 3_110_989_312
    per_expert = 3 * 2048 * 1408
    assert param_count_estimate(cfg) - param_count_estimate(held) == \
        56 * per_expert * 26
    # every held expert is computed for every token; with all 64 held,
    # only the top 6 are active
    assert active_param_count(held) == param_count_estimate(held)
    assert active_param_count(cfg) == \
        param_count_estimate(cfg) - 58 * per_expert * 26


def test_engine_and_pools_take_latent_attention():
    eng = ServeEngine(arch=SMOKE, num_slots=2, max_len=32, kv_layout="paged",
                      page_size=8, num_pages=9, log=lambda *a, **k: None)
    pool = eng.make_pool()
    lanes = mla.latent_lanes(eng.cfg)
    assert lanes == 128
    assert pool.cache["latent"].shape == (3, 9, 8, lanes)
    assert pool.cache["route_counts"].shape == (eng.cfg.num_experts,)
    assert "k" not in pool.cache and "v" not in pool.cache
    assert isinstance(pool, PagedKVCachePool)
    with pytest.raises(NotImplementedError, match="paged"):
        KVCachePool(model_for(eng.cfg), 2, 32)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_engine_serves_and_counts_routing(kernel):
    cfg = smoke_config(SMOKE)
    eng = ServeEngine(arch=SMOKE, num_slots=3, max_len=48, seed=0,
                      kv_layout="paged", kv_kernel=kernel,
                      log=lambda *a, **k: None)
    reqs = zipf_trace(5, cfg.vocab_size, max_prompt=16, max_new=6, seed=1)
    chunked = eng.run(reqs, prefill_chunk=4)
    blocking = eng.run(reqs, prefill_chunk=0)
    toks = lambda st: [r.tokens for r in sorted(st.results,  # noqa: E731
                                                 key=lambda r: r.rid)]
    assert toks(chunked) == toks(blocking)
    assert set(chunked.expert_tokens) == set(range(cfg.num_experts))
    assert chunked.held_pick_share == 1.0
    m = chunked.to_metrics()
    assert m["serve_held_pick_share"] == 1.0
    # every real token, prompt and generated but the last, is routed once
    # per MoE layer, top-k times
    prompt = {r.rid: len(r.prompt) for r in reqs}
    routed = sum(prompt[r.rid] + len(r.tokens) - 1 for r in chunked.results)
    assert sum(chunked.expert_tokens.values()) == routed * 2 * 3
    assert sum(m[f"serve_expert{e}_tokens"] for e in range(8)) == \
        routed * 6
