"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program, so that
the reference (``bench/reference.py``) checks the program against
weights the program did not make.  The tree is laid out as the program's
dense decoder declares it (``models/transformer.DenseLM.param_table``):

    embed/embedding (V, d)       embed/unembed (d, V)
    blocks/ln1, ln2 /scale (L, d) [/bias (L, d) for LayerNorm]
    blocks/attn/wq, wk, wv (L, d, heads, dh)   wo (L, H, dh, d)
    blocks/attn/bq, bk, bv (L, heads, dh)      (qkv bias only)
    blocks/mlp/wi, wg (L, d, f)  wo (L, f, d)
    ln_f/scale (d) [/bias (d)]

``check_layout`` compares it with the program's own table before a run,
so a change of layout stops the run instead of serving other weights.

Matrices are normal with scale 1/sqrt(fan-in), the product of the
contracted sizes; the embedding has scale 0.02.  Norm scales are
1 + 0.1 N(0, 1) and norm and qkv biases 0.1 N(0, 1) and 0.5 N(0, 1),
not ones and zeros, so that the reference checks those terms too.  Each
stacked leaf is drawn one layer at a time (``lax.map``), so the float32
draw of a whole stacked leaf never exists.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16}
# the embedding and unembedding are drawn in this many row blocks
VOCAB_BLOCKS = 16


def shapes(m: dict) -> dict:
    """{path: (per-layer shape, stacked, kind, scale)} for a model dict
    (see ``reference.model_dims``)."""
    d, H, K, dh = m["d"], m["heads"], m["kv_heads"], m["head_dim"]
    f, V = m["d_ff"], m["vocab"]
    ln = {"scale": ((d,), "norm_scale", 0.1)}
    if m["norm"] == "layernorm":
        ln["bias"] = ((d,), "normal", 0.1)
    out = {("embed", "embedding"): ((V, d), False, "normal", 0.02),
           ("embed", "unembed"): ((d, V), False, "normal", 1 / math.sqrt(d))}
    block = {
        ("attn", "wq"): ((d, H, dh), "normal", 1 / math.sqrt(d)),
        ("attn", "wk"): ((d, K, dh), "normal", 1 / math.sqrt(d)),
        ("attn", "wv"): ((d, K, dh), "normal", 1 / math.sqrt(d)),
        ("attn", "wo"): ((H, dh, d), "normal", 1 / math.sqrt(H * dh)),
        ("mlp", "wi"): ((d, f), "normal", 1 / math.sqrt(d)),
        ("mlp", "wg"): ((d, f), "normal", 1 / math.sqrt(d)),
        ("mlp", "wo"): ((f, d), "normal", 1 / math.sqrt(f)),
    }
    if m["qkv_bias"]:
        block[("attn", "bq")] = ((H, dh), "normal", 0.5)
        block[("attn", "bk")] = ((K, dh), "normal", 0.5)
        block[("attn", "bv")] = ((K, dh), "normal", 0.5)
    for norm in ("ln1", "ln2"):
        for k, v in ln.items():
            block[(norm, k)] = v
    for path, (shape, kind, scale) in block.items():
        out[("blocks",) + path] = (shape, True, kind, scale)
    for k, (shape, kind, scale) in ln.items():
        out[("ln_f", k)] = (shape, False, kind, scale)
    return out


def _draw(key, shape, kind, scale, dtype):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    if kind == "norm_scale":
        x = x + 1.0
    return x.astype(dtype)


def _leaf(key, m, shape, stacked, kind, scale, dtype):
    if stacked:
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(m["layers"]))
        return jax.lax.map(lambda k: _draw(k, shape, kind, scale, dtype),
                           keys)
    if shape[0] % VOCAB_BLOCKS == 0 and len(shape) == 2 and shape[0] > 4096:
        rows = shape[0] // VOCAB_BLOCKS
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(VOCAB_BLOCKS))
        blocks = jax.lax.map(
            lambda k: _draw(k, (rows,) + shape[1:], kind, scale, dtype), keys)
        return blocks.reshape(shape)
    return _draw(key, shape, kind, scale, dtype)


def seed_key(seed: int):
    """A PRNG key from a seed of any size (the driver's seeds pass 2**31)."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    key = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(lo))
    return jax.random.fold_in(key, np.uint32(hi))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(frozen: tuple, key, dtype_name: str):
    m = dict(frozen)
    dtype = DTYPES[dtype_name]
    tree: dict = {}
    for path, (shape, stacked, kind, scale) in shapes(m).items():
        leaf_key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _leaf(leaf_key, m, shape, stacked, kind, scale,
                               dtype)
    return tree


def make_weights(m: dict, seed: int, dtype_name: str = "bfloat16"):
    """The whole weight tree for model dict `m`, in one jitted call."""
    return _make(tuple(sorted(m.items())), seed_key(seed), dtype_name)


def check_layout(weights, program_table) -> None:
    """Raise unless `weights` has the program's paths, shapes and dtypes.
    ``program_table`` is a tree of objects with ``shape`` and ``dtype``."""
    got = {jax.tree_util.keystr(p): (tuple(x.shape), np.dtype(x.dtype))
           for p, x in jax.tree_util.tree_leaves_with_path(weights)}
    want = {jax.tree_util.keystr(p): (tuple(x.shape), np.dtype(x.dtype))
            for p, x in jax.tree_util.tree_leaves_with_path(
                program_table, is_leaf=lambda x: hasattr(x, "shape"))}
    if got != want:
        missing = sorted(set(want) - set(got))
        extra = sorted(set(got) - set(want))
        diff = sorted(k for k in set(got) & set(want) if got[k] != want[k])
        raise ValueError(f"weight layout differs from the program's: "
                         f"missing {missing} extra {extra} differ "
                         f"{[(k, got[k], want[k]) for k in diff]}")
