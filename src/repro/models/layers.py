"""Shared neural-net layers (pure functions over param dicts).

Everything here is target-agnostic: activations carry logical-axis
sharding constraints (`shard_constraint`) that the EASEY deployment layer
resolves against the concrete mesh.  Attention has two interchangeable
implementations — the pure-jnp chunked online-softmax path (used on CPU
and as the Pallas oracle) and the Pallas flash kernel the AutoTuner swaps
in for TPU targets (kernels/flash_attention.py).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.params import ParamDef
from repro.sharding.rules import shard_constraint

# ---------------------------------------------------------------------------
# Norms


def _match_dgrad_dtype(fn):
    """Perf iteration I8: norms compute in fp32, so their input cotangent
    comes back fp32 and rides the TP backward all-reduces at 2x the wire
    bytes of the bf16 primal.  Cast the outgoing dx to the primal dtype —
    standard mixed-precision practice (grads accumulate fp32 AFTER the
    reduction)."""
    import functools

    @functools.wraps(fn)
    @jax.custom_vjp
    def wrapped(*args):
        return fn(*args)

    def fwd(*args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp

    def bwd(vjp, g):
        grads = vjp(g)
        # dx (the residual-stream cotangent) matches the primal dtype = the
        # cotangent's own dtype; small param grads stay fp32.
        return (grads[0].astype(g.dtype),) + tuple(grads[1:])

    wrapped.defvjp(fwd, bwd)
    return wrapped


@_match_dgrad_dtype
def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


@_match_dgrad_dtype
def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_defs(d_model: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": ParamDef((d_model,), ("embed",), init="ones")}
    return {"scale": ParamDef((d_model,), ("embed",), init="ones"),
            "bias": ParamDef((d_model,), ("embed",), init="zeros")}


def apply_norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# Rotary position embeddings


def rope_frequencies(head_dim: int, fraction: float, theta: float) -> int:
    """Number of rotated dims (even)."""
    rot = int(head_dim * fraction)
    return rot - rot % 2


def apply_rope(x: jax.Array, positions: jax.Array, *, fraction: float = 1.0,
               theta: float = 10000.0) -> jax.Array:
    """x: (b, s, heads, head_dim); positions: (b, s) int32."""
    head_dim = x.shape[-1]
    rot = rope_frequencies(head_dim, fraction, theta)
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = jnp.exp(-jnp.arange(0, rot, 2, dtype=jnp.float32)
                    * (math.log(theta) / rot))
    out = rotate_pairs(x_rot, positions, freqs)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < head_dim else out


def rotate_pairs(x: jax.Array, positions: jax.Array,
                 freqs: jax.Array) -> jax.Array:
    """Rotate adjacent pairs (2i, 2i+1) of x: (b, s, heads, 2 * len(freqs))
    by ``positions * freqs[i]``; positions: (b, s)."""
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, rot/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((seq_len, d_model), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe


# ---------------------------------------------------------------------------
# Attention (GQA).  Reference chunked online-softmax implementation.

_Q_CHUNK = 1024


def _attn_one_chunk(q, k, v, mask, scale):
    """q: (b,K,G,qc,dh)  k: (b,t,K,dh)  v: (b,t,K,dh)
    mask: (qc,t) bool, or (b,qc,t) for per-row masks (slot-wise decode)."""
    scores = jnp.einsum("bkgqd,btkd->bkgqt", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bkgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def dot_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, q_offset: jax.Array | int = 0,
                  kv_len: jax.Array | None = None,
                  q_chunk: int = _Q_CHUNK,
                  scale: float | None = None) -> jax.Array:
    """Grouped-query attention.

    q: (b, s, H, dh); k: (b, t, K, dh), v: (b, t, K, dv) with H % K == 0;
    returns (b, s, H, dv).  ``scale`` defaults to dh ** -0.5.
    causal: query i attends keys j <= i + q_offset.
    kv_len: optional valid-length of the kv sequence (decode with a
        pre-allocated cache).
    q_offset / kv_len may also be (b,) vectors — per-row lengths for the
    continuous-batching slot decode, producing a (b, qc, t) mask.
    Long sequences are processed in q-chunks via lax.map so the live score
    buffer is (b, H, q_chunk, t) instead of (b, H, s, t).
    """
    b, s, H, dh = q.shape
    t, K, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, s, K, G, dh).transpose(0, 2, 3, 1, 4)  # b,K,G,s,dh

    kv_pos = jnp.arange(t)
    per_row = jnp.ndim(q_offset) == 1 or \
        (kv_len is not None and jnp.ndim(kv_len) == 1)
    if not per_row:
        valid = kv_pos < (kv_len if kv_len is not None else t)

    def mask_for(q_pos):
        if per_row:
            m = jnp.ones((b, q_pos.shape[0], t), bool)
            if kv_len is not None:
                m = m & (kv_pos[None, None, :]
                         < jnp.reshape(jnp.asarray(kv_len), (-1, 1, 1)))
            if causal:
                off = jnp.reshape(jnp.asarray(q_offset), (-1, 1, 1))
                m = m & (kv_pos[None, None, :] <= (q_pos[None, :, None] + off))
            return m
        m = valid[None, :]
        if causal:
            m = m & (kv_pos[None, :] <= (q_pos[:, None] + q_offset))
        return jnp.broadcast_to(m, (q_pos.shape[0], t))

    if s <= q_chunk:
        out = _attn_one_chunk(qg, k, v, mask_for(jnp.arange(s)), scale)
    else:
        assert s % q_chunk == 0, (s, q_chunk)
        n = s // q_chunk
        qc = qg.reshape(b, K, G, n, q_chunk, dh).transpose(3, 0, 1, 2, 4, 5)

        # perf iteration I4: checkpoint the chunk body so AD re-derives the
        # (q_chunk x t) scores/probs in the backward instead of stacking
        # them for all chunks (full s x t score matrix in HBM).
        @jax.checkpoint
        def one(args):
            i, qi = args
            q_pos = i * q_chunk + jnp.arange(q_chunk)
            return _attn_one_chunk(qi, k, v, mask_for(q_pos), scale)

        out = jax.lax.map(one, (jnp.arange(n), qc))          # n,b,K,G,qc,dv
        out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, K, G, s, dv)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, H, dv)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache handling)


def _layer_of(cache: jax.Array, layer) -> jax.Array:
    """One layer's cache: ``cache`` itself where ``layer`` is None, else
    layer ``layer`` of a (layers, ...) stack (a dynamic index XLA fuses
    into the op that reads it)."""
    if layer is None:
        return cache
    return jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)


def _in_layer(ids: jax.Array, layer, n: int) -> jax.Array:
    """Page or token-row ids of one layer (``n`` of them per layer) as ids
    into the whole stack with its leading dims merged; unchanged for a
    one-layer cache."""
    return ids if layer is None else layer * n + ids


def _set_rows(pool: jax.Array, rows: jax.Array, new: jax.Array) -> jax.Array:
    """``new`` (..., K, dh) written at token rows ``rows`` of a page pool
    (its dims before a token's ``(rows, lanes)`` merged, the heads packed
    as the pool stores them: ``serving/pool.page_rows``) — a scatter in
    place on the donated (or scan-carried) buffer."""
    row = pool.shape[-2:]
    new = new.reshape(new.shape[:-2] + row)
    return pool.reshape((-1,) + row).at[rows].set(new).reshape(pool.shape)


def paged_rows(pages: jax.Array, pos: jax.Array, psize: int) -> jax.Array:
    """Flat token rows (page * psize + offset, within one layer) of
    positions ``pos`` through page-table rows ``pages`` (``(..., max_pages)``,
    ``pos`` ``(..., n)``); a position past the table lands in the reserved
    junk page 0, never wrapped into a page a live request may share."""
    max_pages = pages.shape[-1]
    logical = pos // psize
    dest = jnp.take_along_axis(pages, jnp.minimum(logical, max_pages - 1),
                               axis=-1)
    return jnp.where(logical < max_pages, dest * psize + pos % psize,
                     pos % psize)


def _take_pages(pool: jax.Array, ids: jax.Array) -> jax.Array:
    """The pages ``ids`` of a page pool whose dims before (page_size,
    rows, lanes) are merged, read in place (no slice of a layer first)."""
    return jnp.take(pool.reshape((-1,) + pool.shape[-3:]), ids, axis=0)


def attention_defs(cfg) -> dict:
    dh = cfg.head_dim
    d = {
        "wq": ParamDef((cfg.d_model, cfg.num_heads, dh), ("embed", "heads", "head_dim")),
        "wk": ParamDef((cfg.d_model, cfg.num_kv_heads, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((cfg.d_model, cfg.num_kv_heads, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((cfg.num_heads, dh, cfg.d_model), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamDef((cfg.num_heads, dh), ("heads", "head_dim"), init="zeros")
        d["bk"] = ParamDef((cfg.num_kv_heads, dh), ("kv_heads", "head_dim"), init="zeros")
        d["bv"] = ParamDef((cfg.num_kv_heads, dh), ("kv_heads", "head_dim"), init="zeros")
    return d


def attention(p: dict, x: jax.Array, cfg, mesh, *, positions: jax.Array,
              mode: str, cache: dict | None = None,
              kv_source: jax.Array | None = None,
              window: int | None = None):
    """mode: 'full' (train / prefill-like, causal unless cross),
    'prefill' (causal + returns fresh cache), 'decode' (uses cache),
    'chunk' (chunked prefill written straight into a serving KV pool).

    kv_source: if given, cross-attention (keys/values from encoder output,
    non-causal, no rope on kv positions beyond source positions).
    Returns (out, new_cache).

    The device trace names each part by ``jax.named_scope``: ``qkv``,
    ``kv_write`` (the new K/V into the cache and the reshapes that feed
    the read), ``attn`` (the kernel or the gather and softmax) and
    ``out_proj``.
    """
    b, s, _ = x.shape
    cross = kv_source is not None
    src = kv_source if cross else x
    with jax.named_scope("qkv"):
        q = jnp.einsum("bse,ehd->bshd", x, p["wq"])
        k = jnp.einsum("bte,ekd->btkd", src, p["wk"])
        v = jnp.einsum("bte,ekd->btkd", src, p["wv"])
        if "bq" in p:
            q = q + p["bq"][None, None]
            k = k + p["bk"][None, None]
            v = v + p["bv"][None, None]
        q = shard_constraint(q, ("act_batch", "act_seq", "act_heads", None),
                             mesh)
        k = shard_constraint(k, ("act_batch", "act_seq", "act_kv_heads",
                                 None), mesh)
        v = shard_constraint(v, ("act_batch", "act_seq", "act_kv_heads",
                                 None), mesh)
        if cfg.pos == "rope" and not cross:
            q = apply_rope(q, positions, fraction=cfg.rope_fraction,
                           theta=cfg.rope_theta)
            k = apply_rope(k, positions, fraction=cfg.rope_fraction,
                           theta=cfg.rope_theta)

    new_cache = None
    if mode == "decode":
        assert cache is not None and not cross
        # int32 tokens seen so far: (b,) per slot with pages, else scalar
        idx = cache["index"]
        # the layer scan passes the WHOLE cache (a leading layers dim) and
        # this layer's id: every write lands in place on that buffer and
        # every read indexes its layer, so no layer's cache is sliced out
        # and written back.  Without "layer" the cache is one layer's.
        layer = cache.get("layer")
        lead = () if layer is None else (layer,)
        t = cache["k"].shape[-3]
        if "pages" in cache:
            # SLOT-WISE decode (continuous batching over the serving KV
            # pool): every row is a pool slot at its own length `idx`,
            # this layer's cache is a page pool (num_pages, page_size,
            # rows, lanes) — a token's (K, dh) as serving/pool.page_rows
            # packs it — and `pages` is the (slots, max_pages) int32 page
            # table.  The new kv is scattered to each row's own
            # page/offset; K/V are then read back *through the page table*
            # (one gather per row) as a (slots, max_pages*page_size, K, dh)
            # tensor under a per-row causal-with-length mask.  The window
            # is not applied: pool slots are already bounded by max_len.
            # Rows with a zeroed page-table entry (freed / never-allocated
            # slots) write into the reserved junk page 0 (of this layer),
            # which no live table references.
            pages = cache["pages"]
            n_pages, psize = cache["k"].shape[-4:-2]
            max_pages = pages.shape[1]
            Kh, dh = k.shape[2], k.shape[3]
            with jax.named_scope("kv_write"):
                # each row writes its positions idx..idx+s-1 (s > 1: a
                # VERIFY burst); a position past the slot's page run (a
                # slot at capacity) goes to the reserved junk page 0, never
                # wrapped into the slot's last page, which under the prefix
                # cache may be shared with a live request
                pos = idx[:, None] + jnp.arange(s)[None, :]  # (slots, s)
                fpos = _in_layer(paged_rows(pages, pos, psize), layer,
                                 n_pages * psize)
                k_all = _set_rows(cache["k"], fpos, k)
                v_all = _set_rows(cache["v"], fpos, v)
            with jax.named_scope("attn"):
                if cache.get("use_kernel") and s == 1:
                    # fused Pallas path (single-token decode only; verify
                    # bursts take the gather path): the page table is
                    # walked inside the kernel, so the materialized
                    # (slots, max_pages*psize, K, dh) gather never hits HBM
                    from repro.kernels.ops import paged_attention
                    out = paged_attention(
                        q[:, 0], k_all, v_all, pages,
                        (idx + s).astype(jnp.int32), layer)[:, None]
                else:
                    ids = _in_layer(pages, layer, n_pages)
                    kg = _take_pages(k_all, ids).reshape(
                        q.shape[0], max_pages * psize, Kh, dh)
                    vg = _take_pages(v_all, ids).reshape(
                        q.shape[0], max_pages * psize, Kh, dh)
                    out = dot_attention(q, kg, vg, causal=True,
                                        q_offset=idx, kv_len=idx + s)
        else:
            # DECODE at one scalar index for the whole batch
            # (build_decode_step).  As a RING BUFFER (a window no longer
            # than the cache) the cache holds only the last `t` positions:
            # keys carry absolute RoPE phases from write time, so order in
            # the buffer is irrelevant; everything valid is attendable.
            ring = window is not None and t <= window
            with jax.named_scope("kv_write"):
                write = jnp.mod(idx, t) if ring else idx
                at = lead + (0, write, 0, 0)
                one = (1,) * len(lead)
                k_all = jax.lax.dynamic_update_slice(
                    cache["k"], k.reshape(one + k.shape), at)
                v_all = jax.lax.dynamic_update_slice(
                    cache["v"], v.reshape(one + v.shape), at)
            with jax.named_scope("attn"):
                k_l, v_l = _layer_of(k_all, layer), _layer_of(v_all, layer)
                if ring:
                    out = dot_attention(q, k_l, v_l, causal=False,
                                        kv_len=jnp.minimum(idx + s, t))
                else:
                    out = dot_attention(q, k_l, v_l, causal=True,
                                        q_offset=idx, kv_len=idx + s)
        new_cache = {"k": k_all, "v": v_all, "index": idx + s}
    elif mode == "chunk":
        # CHUNKED PREFILL written straight into the serving pool: x is one
        # bucketed chunk (batch 1, s tokens at global positions
        # [offset, offset+s)) of a single request's prompt, and the cache
        # is the pool's own storage — a page pool (num_pages, page_size,
        # rows, lanes) plus the slot's (max_pages,) page-table row; with
        # "layer", every layer's storage, written and read at that layer
        # in place (as decode).  The chunk's K/V scatter to their final
        # pages (no intermediate (1, s) cache to re-scatter later), then
        # the slot's pages are read back so the chunk attends causally
        # over every prior chunk through the same indirection decode uses.
        # Bucket padding rows (query j >= the true chunk length) write
        # junk only at positions later chunks / decode overwrite before
        # any mask admits them; out-of-range rows land in the reserved
        # junk page 0.
        assert cache is not None and not cross
        off = cache["offset"]
        layer = cache.get("layer")
        # kv_bound (a STATIC python int >= offset + s) caps the read-back:
        # a 4-token prompt in a max_len=128 pool attends 4-16 positions,
        # not 128.  Bounds are bucketed to powers of two host-side so the
        # jit cache stays (chunk buckets) x (bound buckets).
        bound = cache["kv_bound"]
        pos = off + jnp.arange(s)                   # (s,) global positions
        Kh, dh = k.shape[2], k.shape[3]
        pages_row = cache["pages_row"]              # (max_pages,) int32
        n_pages, psize = cache["k"].shape[-4:-2]
        max_pages = pages_row.shape[0]
        with jax.named_scope("kv_write"):
            fpos = _in_layer(paged_rows(pages_row, pos, psize), layer,
                             n_pages * psize)
            k_all = _set_rows(cache["k"], fpos, k[0])
            v_all = _set_rows(cache["v"], fpos, v[0])
        with jax.named_scope("attn"):
            B = min(-(-bound // psize), max_pages)
            ids = _in_layer(pages_row[:B], layer, n_pages)
            kg = _take_pages(k_all, ids).reshape(1, B * psize, Kh, dh)
            vg = _take_pages(v_all, ids).reshape(1, B * psize, Kh, dh)
            out = dot_attention(q, kg, vg, causal=True, q_offset=off,
                                kv_len=off + s)
        new_cache = {"k": k_all, "v": v_all}
    else:
        causal = (not cross) and cfg.causal
        with jax.named_scope("attn"):
            if window is not None and s > window and causal:
                out = _windowed_attention(q, k, v, window)
            else:
                out = dot_attention(q, k, v, causal=causal)
        if mode == "prefill" and not cross:
            new_cache = {"k": k, "v": v, "index": jnp.asarray(s, jnp.int32)}

    with jax.named_scope("out_proj"):
        out = shard_constraint(out, ("act_batch", "act_seq", "act_heads",
                                     None), mesh)
        y = jnp.einsum("bshd,hde->bse", out, p["wo"])
        y = shard_constraint(y, ("act_batch", "act_seq", "act_embed"), mesh)
    return y, new_cache


def _windowed_attention(q, k, v, window: int) -> jax.Array:
    """Sliding-window causal attention via q-chunking: each q-chunk only
    sees the kv slice [chunk_start - window, chunk_end)."""
    b, s, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(dh)
    qc = min(_Q_CHUNK, s)
    assert s % qc == 0
    n = s // qc
    span = qc + window  # kv window per chunk
    qg = q.reshape(b, n, qc, K, G, dh).transpose(1, 0, 3, 4, 2, 5)

    k_pad = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    v_pad = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    def one(args):
        i, qi = args
        start = i * qc  # in padded coords the window begins at start
        ks = jax.lax.dynamic_slice_in_dim(k_pad, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v_pad, start, span, axis=1)
        q_pos = start + jnp.arange(qc)          # unpadded positions
        kv_pos = start - window + jnp.arange(span)
        m = (kv_pos[None, :] <= q_pos[:, None]) & \
            (kv_pos[None, :] > q_pos[:, None] - window) & (kv_pos[None, :] >= 0)
        return _attn_one_chunk(qi, ks, vs, m, scale)

    out = jax.lax.map(one, (jnp.arange(n), qg))   # n,b,K,G,qc,dh
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(b, K, G, s, dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, H, dh)


# ---------------------------------------------------------------------------
# MLP


def mlp_defs(cfg) -> dict:
    gated = cfg.activation in ("silu", "geglu")
    d = {"wi": ParamDef((cfg.d_model, cfg.d_ff), ("embed", "mlp")),
         "wo": ParamDef((cfg.d_ff, cfg.d_model), ("mlp", "embed"))}
    if gated:
        d["wg"] = ParamDef((cfg.d_model, cfg.d_ff), ("embed", "mlp"))
    return d


def mlp(p: dict, x: jax.Array, cfg, mesh) -> jax.Array:
    h = jnp.einsum("bse,ef->bsf", x, p["wi"])
    if cfg.activation == "silu":
        h = jax.nn.silu(h) if "wg" not in p else \
            jax.nn.silu(jnp.einsum("bse,ef->bsf", x, p["wg"])) * h
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(jnp.einsum("bse,ef->bsf", x, p["wg"])) * h
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(h)
    elif cfg.activation == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(cfg.activation)
    h = shard_constraint(h, ("act_batch", "act_seq", "act_experts"), mesh)
    y = jnp.einsum("bsf,fe->bse", h, p["wo"])
    return shard_constraint(y, ("act_batch", "act_seq", "act_embed"), mesh)


# ---------------------------------------------------------------------------
# Embedding / unembedding


def embed_defs(cfg) -> dict:
    # NOTE (perf iteration I3, REFUTED): feature-sharding the input table
    # (vocab replicated, features over 'model') makes the token gather
    # local and kills the SPMD "involuntary full rematerialization"
    # warning — but its backward scatter trips an XLA SPMD verifier bug
    # ("slice dim size d_model > d_model/16") on every non-SP train cell.
    # Reverted to vocab-sharded; the inefficiency is priced into the
    # roofline and logged in EXPERIMENTS.md §Perf.
    d = {"embedding": ParamDef((cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"),
                               init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        d["unembed"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    if cfg.pos == "learned":
        d["pos_embedding"] = ParamDef((cfg.max_position, cfg.d_model),
                                      (None, "embed"), init="embed", scale=0.02)
    return d


def embed(p: dict, tokens: jax.Array, cfg, mesh, positions=None) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0).astype(cfg.activation_dtype)
    if cfg.pos == "learned":
        assert positions is not None
        x = x + jnp.take(p["pos_embedding"], positions, axis=0).astype(x.dtype)
    elif cfg.pos == "sinusoidal":
        pe = sinusoidal_positions(cfg.max_position, cfg.d_model)
        x = x + jnp.take(pe, positions, axis=0).astype(x.dtype)
    return shard_constraint(x, ("act_batch", "act_seq", "act_embed"), mesh)


def unembed(p: dict, x: jax.Array, cfg, mesh) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bse,ve->bsv", x, p["embedding"].astype(x.dtype))
    else:
        logits = jnp.einsum("bse,ev->bsv", x, p["unembed"])
    return shard_constraint(logits, ("act_batch", "act_seq", "act_vocab"), mesh)


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None):
    """Mean per-token cross entropy in fp32. labels: int32 (b, s)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
