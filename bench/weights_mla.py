"""Random weights of DeepSeek-V2 (latent attention, DeepSeekMoE) from
``--seed``, made on the device in one jitted call, as
``bench/weights.py`` makes the dense decoders'.

The tree is the program's layout (``models/moe.MoELM.param_table`` with
``models/mla.mla_defs``), which keeps the published matrices whole:

    embed/embedding (V, d)             embed/unembed (d, V)
    dense_blocks/...  (Ld, ...)        blocks/... (L - Ld, ...), each with
      ln1, ln2/scale (d)
      attn/wq (d, H, nope + rope)      q_proj
      attn/wkv_a (d, rank + rope)      kv_a_proj_with_mqa
      attn/kv_norm/scale (rank)        kv_a_layernorm
      attn/wkv_b (rank, H, nope + v)   kv_b_proj
      attn/wo (H, v, d)                o_proj
    dense_blocks/mlp/wi, wg (d, f)  wo (f, d)
    blocks/mlp/router (d, E)           the gate over all published experts
    blocks/mlp/wi, wg (held, d, fe)  wo (held, fe, d)   the held experts
    blocks/mlp/shared/wi, wg (d, S fe)  wo (S fe, d)    the shared experts
    ln_f/scale (d)

Scales as ``bench/weights.py``: matrices normal with scale 1/sqrt(fan-in),
the embedding 0.02, norm scales 1 + 0.1 N(0, 1).  ``check_layout`` is
``bench/weights.py``'s.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

from bench.weights import (DTYPES, _draw, _leaf, check_layout,  # noqa: F401
                           seed_key)


def _block(m: dict, moe: bool) -> dict:
    d, H, r = m["d"], m["heads"], m["rank"]
    n, e, v = m["nope"], m["rope"], m["v"]
    out = {
        ("ln1", "scale"): ((d,), "norm_scale", 0.1),
        ("ln2", "scale"): ((d,), "norm_scale", 0.1),
        ("attn", "wq"): ((d, H, n + e), "normal", 1 / math.sqrt(d)),
        ("attn", "wkv_a"): ((d, r + e), "normal", 1 / math.sqrt(d)),
        ("attn", "kv_norm", "scale"): ((r,), "norm_scale", 0.1),
        ("attn", "wkv_b"): ((r, H, n + v), "normal", 1 / math.sqrt(r)),
        ("attn", "wo"): ((H, v, d), "normal", 1 / math.sqrt(H * v)),
    }
    if not moe:
        f = m["d_ff"]
        out[("mlp", "wi")] = ((d, f), "normal", 1 / math.sqrt(d))
        out[("mlp", "wg")] = ((d, f), "normal", 1 / math.sqrt(d))
        out[("mlp", "wo")] = ((f, d), "normal", 1 / math.sqrt(f))
        return out
    f, E, S = m["moe_d_ff"], m["held"], m["shared"] * m["moe_d_ff"]
    out[("mlp", "router")] = ((d, m["experts"]), "normal", 1 / math.sqrt(d))
    out[("mlp", "wi")] = ((E, d, f), "normal", 1 / math.sqrt(d))
    out[("mlp", "wg")] = ((E, d, f), "normal", 1 / math.sqrt(d))
    out[("mlp", "wo")] = ((E, f, d), "normal", 1 / math.sqrt(f))
    out[("mlp", "shared", "wi")] = ((d, S), "normal", 1 / math.sqrt(d))
    out[("mlp", "shared", "wg")] = ((d, S), "normal", 1 / math.sqrt(d))
    out[("mlp", "shared", "wo")] = ((S, d), "normal", 1 / math.sqrt(S))
    return out


def shapes(m: dict) -> dict:
    """{path: (per-layer shape, layers stacked (0: none), kind, scale)}."""
    d, V = m["d"], m["vocab"]
    out = {("embed", "embedding"): ((V, d), 0, "normal", 0.02),
           ("embed", "unembed"): ((d, V), 0, "normal", 1 / math.sqrt(d)),
           ("ln_f", "scale"): ((d,), 0, "norm_scale", 0.1)}
    n0 = m["dense_layers"]
    for stack, n, moe in (("dense_blocks", n0, False),
                          ("blocks", m["layers"] - n0, True)):
        if n:
            for path, (shape, kind, scale) in _block(m, moe).items():
                out[(stack,) + path] = (shape, n, kind, scale)
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(frozen: tuple, key, dtype_name: str):
    m = dict(frozen)
    dtype = DTYPES[dtype_name]
    tree: dict = {}
    for path, (shape, n, kind, scale) in shapes(m).items():
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        if n:
            keys = jax.vmap(lambda i, k=leaf_key: jax.random.fold_in(k, i))(
                jnp.arange(n))
            node[path[-1]] = jax.lax.map(
                lambda k, s=shape, kd=kind, sc=scale: _draw(k, s, kd, sc,
                                                            dtype), keys)
        else:
            node[path[-1]] = _leaf(leaf_key, m, shape, False, kind, scale,
                                   dtype)
    return tree


def make_weights(m: dict, seed: int, dtype_name: str = "bfloat16"):
    """The whole weight tree for model dict `m`, in one jitted call."""
    return _make(tuple(sorted(m.items())), seed_key(seed), dtype_name)
