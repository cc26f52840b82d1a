"""Multi-head latent attention (MLA), as DeepSeek-V2 publishes it.

Per layer, with H heads, rank r = ``kv_lora_rank``, n = ``qk_nope_head_dim``,
e = ``qk_rope_head_dim`` and v = ``v_head_dim`` (no q-LoRA):

    q            = x wq                     (H, n + e): q_nope, q_pe
    [c_kv, k_pe] = x wkv_a                  (r + e)
    c_kv         = RMSNorm(c_kv)            (kv_a_layernorm)
    [k_nope, v]  = c_kv wkv_b               (H, n + v)
    q_pe, k_pe   = rope(q_pe), rope(k_pe)   YaRN frequencies, k_pe one head
    o_h          = softmax(scale * [q_nope, q_pe] . [k_nope, k_pe]) v
    out          = concat_h(o_h) wo

with ``scale = (n + e) ** -0.5 * mscale ** 2`` under YaRN (``softmax_scale``).

The cache holds one latent row per token: the normalised ``c_kv`` and the
rotated ``k_pe``, zero-padded to ``latent_lanes`` (a whole number of
128-lane tiles), never a per-head K or V.  Every mode computes the
absorbed form: ``wkv_b``'s key half goes into the query
(``q_abs = q_nope wkv_b[:, :, :n]``, rank r per head) and its value half
into the output (``o_h = p . c_kv``, then ``o_h wkv_b[:, h, n:]``), so
each head attends the latent rows themselves — multi-query attention
whose keys are whole rows and whose values are their first r lanes.
Single-token decode over a paged pool runs the Pallas kernel
(``kernels/mla_attention.py``) when the step asks for it; chunks, verify
bursts and the gather path read the slot's pages and attend them by
``layers.dot_attention`` with one kv head.

Rotated pairs are adjacent dims (2i, 2i+1): DeepSeek-V2's reference code
regroups q_pe and k_pe as (e/2, 2) before its ``rotate_half``, which is
the same pairing.

The device trace names the parts: ``mla_q`` (projections, norm, rope and
the absorption), ``kv_write``, ``attn`` and ``out_proj``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models.params import ParamDef

LANES = 128


def mla_defs(cfg) -> dict:
    H, d, r = cfg.num_heads, cfg.d_model, cfg.kv_lora_rank
    n, e, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamDef((d, H, n + e), ("embed", "heads", "head_dim")),
        "wkv_a": ParamDef((d, r + e), ("embed", None)),
        "kv_norm": {"scale": ParamDef((r,), (None,), init="ones")},
        "wkv_b": ParamDef((r, H, n + v), (None, "heads", "head_dim")),
        "wo": ParamDef((H, v, d), ("heads", "head_dim", "embed")),
    }


def latent_lanes(cfg) -> int:
    """Lanes of one stored latent row: c_kv and k_pe, padded to 128s."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return -(-width // LANES) * LANES


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def rope_freqs(cfg) -> np.ndarray:
    """Inverse frequencies of the e rope dims, float32: plain RoPE at
    ``rope_theta``, or YaRN's blend of the original and the interpolated
    (÷ factor) frequencies along a linear ramp between the correction
    dims of ``beta_fast`` and ``beta_slow`` rotations."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    y = cfg.rope_yarn
    if y is None:
        return extra

    def correction_dim(rotations):
        return dim * math.log(y.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp            # share of the original frequency
    return (extra / y.factor * (1 - keep) + extra * keep).astype(np.float32)


def _rope(x, positions, cfg):
    """YaRN rope of x: (b, s, heads, e), with its attention factor."""
    out = L.rotate_pairs(x, positions, jnp.asarray(rope_freqs(cfg)))
    y = cfg.rope_yarn
    if y is not None:
        factor = yarn_mscale(y.factor, y.mscale) / \
            yarn_mscale(y.factor, y.mscale_all_dim)
        if factor != 1.0:
            out = (out.astype(jnp.float32) * factor).astype(x.dtype)
    return out


def _pad(x, lanes):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - x.shape[-1])])


def _take_pages(pool, ids):
    """Pages ``ids`` (indices into the pool's merged (layers * pages)) as
    ``ids.shape + (page_size, lanes)``, read in place."""
    return jnp.take(pool.reshape((-1,) + pool.shape[-2:]), ids, axis=0)


def _set_rows(pool, rows, new):
    """``new`` (..., lanes) written at flat token rows ``rows`` of the
    pool (its dims before a row merged), in place."""
    lanes = pool.shape[-1]
    return pool.reshape(-1, lanes).at[rows.reshape(-1)].set(
        new.reshape(-1, lanes)).reshape(pool.shape)


def attention(p: dict, x: jax.Array, cfg, mesh, *, positions: jax.Array,
              mode: str, cache: dict | None = None):
    """Latent attention of x: (b, s, d).  ``mode`` as ``layers.attention``:
    'full' and 'prefill' attend the sequence itself ('prefill' returns
    its latent rows as the cache), 'decode' and 'chunk' write their rows
    into a paged latent pool (``cache["latent"]``, every layer's, at
    ``cache["layer"]``) and attend through the page table.
    Returns (out, new_cache)."""
    b, s, _ = x.shape
    r, n = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    lanes = latent_lanes(cfg)
    scale = softmax_scale(cfg)
    with jax.named_scope("mla_q"):
        q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
        kv = jnp.einsum("bse,ec->bsc", x, p["wkv_a"])
        c = L.rmsnorm(kv[..., :r], p["kv_norm"]["scale"])
        k_pe = _rope(kv[..., None, r:], positions, cfg)[:, :, 0]
        row = _pad(jnp.concatenate([c, k_pe], -1), lanes)    # (b, s, lanes)
        q_abs = jnp.einsum("bshn,rhn->bshr", q[..., :n],
                           p["wkv_b"][..., :n])
        q_lat = _pad(jnp.concatenate(
            [q_abs, _rope(q[..., n:], positions, cfg)], -1), lanes)

    def attend(rows, **kw):
        """The heads over latent rows (b, t, lanes): (b, s, H, r)."""
        return L.dot_attention(q_lat, rows[:, :, None],
                               rows[:, :, None, :r], scale=scale, **kw)

    new_cache = None
    if mode in ("decode", "chunk"):
        pool, layer = cache["latent"], cache["layer"]
        n_pages, psize = pool.shape[-3:-1]
        at = layer * n_pages
        if mode == "decode":
            idx = cache["index"]                        # (b,) tokens held
            pages = cache["pages"]                      # (b, max_pages)
            pos = idx[:, None] + jnp.arange(s)[None, :]
            with jax.named_scope("kv_write"):
                rows = L.paged_rows(pages, pos, psize) + at * psize
                pool = _set_rows(pool, rows, row)
            with jax.named_scope("attn"):
                if cache.get("use_kernel") and s == 1:
                    from repro.kernels.ops import mla_decode_attention
                    # a slot with no page (free, or mid-prefill: its row
                    # of the table is zeroed) has nothing to attend
                    live = jnp.where(pages[:, 0] != 0, idx + 1, 0)
                    o = mla_decode_attention(
                        q_lat[:, 0], pool, pages, live, layer, scale=scale,
                        value_lanes=r)[:, None]
                else:
                    lat = _take_pages(pool, at + pages).reshape(b, -1, lanes)
                    o = attend(lat, causal=True, q_offset=idx,
                               kv_len=idx + s)
            new_cache = {"latent": pool, "index": idx + s}
        else:
            off, pages_row = cache["offset"], cache["pages_row"]
            pos = off + jnp.arange(s)
            with jax.named_scope("kv_write"):
                rows = L.paged_rows(pages_row, pos, psize) + at * psize
                pool = _set_rows(pool, rows, row[0])
            with jax.named_scope("attn"):
                B = min(-(-cache["kv_bound"] // psize), pages_row.shape[0])
                lat = _take_pages(pool, at + pages_row[:B]).reshape(
                    1, B * psize, lanes)
                o = attend(lat, causal=True, q_offset=off, kv_len=off + s)
            new_cache = {"latent": pool}
    else:
        with jax.named_scope("attn"):
            o = attend(row, causal=True)
        if mode == "prefill":
            new_cache = {"latent": row, "index": jnp.asarray(s, jnp.int32)}

    with jax.named_scope("out_proj"):
        o = jnp.einsum("bshr,rhv->bshv", o, p["wkv_b"][..., n:])
        y = jnp.einsum("bshv,hve->bse", o, p["wo"])
    return y, new_cache
