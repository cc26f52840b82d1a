"""Plain float32 reference forward of the dense decoders the cells serve,
and the comparison that decides a run's ``correct``.

It imports nothing of the program.  Weights come from ``bench/weights.py``
in the tree the program is also given.  The forward follows the
published architectures (Llama for DeepSeek LLM, StableLM 2) in
``jax.numpy`` under ``default_matmul_precision("highest")``, with no
kernel, cache or batching: one sequence at a time, layer by layer, every
layer's weights upcast to float32 only while it runs, so that it fits
beside the served model's weights on one chip.

* RMSNorm (eps 1e-6) or LayerNorm with bias (eps 1e-5), in float32.
* RoPE on the first ``partial_rotary_factor`` share of each head, with
  theta from the config.  Rotated pairs are adjacent dims (2i, 2i+1), as
  the program lays out its projections.  Hugging Face's ``rotate_half``
  pairs dim i with i + rot/2; the two differ by a fixed permutation of the
  columns of wq and wk, which random weights do not tell apart.
* Optional q, k, v biases; SwiGLU MLP ``wo(silu(x wg) * (x wi))``.
* Causal softmax attention over the whole sequence, in float32.

The served model is judged per served token, by two readings
(``readings``):

* ``max_gap``: the widest gap by which the reference's logit of the
  token served lies below the reference's best logit at that position
  (0 where the pick is the reference's argmax);
* ``logit_err``: the widest distance between the logit the pick was made
  from (the largest logit of the row, for a greedy pick) and the
  reference's logit of the same token.  Where the picks agree with the
  reference's, as they do at long contexts whose continuations are
  confident, the gap reads 0 in any precision; the logit itself still
  shows the precision it was computed in.

``precision="fp8"`` is the control: the same forward with every matmul
input, weights and activations, rounded to float8 e4m3 with one scale per
tensor, the step below the bfloat16 the configurations state.
``control_picks`` puts it in the program's place: at the same positions
of the same sampled requests, its greedy pick and the logit it was made
from, scored by the same ``readings`` against the same limits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
FP8_MAX = 448.0
PRECISIONS = ("f32", "fp8")
# sequences are padded to a power of two at least this long, so a run
# compiles a few layer programs, not one per length
MIN_BUCKET = 256
# vocab columns of the unembedding upcast at once
VOCAB_BLOCK = 8192
# query rows per attention block (bounds the score buffer)
Q_BLOCK = 512


def model_dims(cfg: dict) -> dict:
    """The reference's view of a configuration file's ``config`` (the
    published Hugging Face keys)."""
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    norm = "layernorm" if "layer_norm_eps" in cfg else "rmsnorm"
    return {
        "d": d, "heads": heads,
        "kv_heads": int(cfg.get("num_key_value_heads", heads)),
        "head_dim": int(cfg.get("head_dim") or d // heads),
        "d_ff": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "layers": int(cfg["num_hidden_layers"]),
        "norm": norm,
        "eps": float(cfg.get("layer_norm_eps", cfg.get("rms_norm_eps",
                                                       RMS_EPS))),
        "qkv_bias": bool(cfg.get("use_qkv_bias",
                                 cfg.get("attention_bias", False))),
        "rope_fraction": float(cfg.get("partial_rotary_factor", 1.0)),
        "rope_theta": float(cfg.get("rope_theta", 10000.0)),
    }


def _q(x, precision):
    """Round a matmul input to the control's precision (identity for f32)."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _norm(x, p, m):
    if m["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["eps"])
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m["eps"]) * p["scale"] + p["bias"]


def _rope(x, m):
    """x: (s, heads, dh), positions 0..s-1."""
    s, _, dh = x.shape
    rot = int(dh * m["rope_fraction"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = m["rope_theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.reshape(s, -1, rot), x[..., rot:]], -1)


def _mm(a, b, precision, spec):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block(blocks, layer, x, frozen, precision):
    """Decoder layer `layer` of the stacked `blocks` over one sequence
    x: (s, d) float32."""
    m = dict(frozen)
    p = jax.tree.map(lambda w: w[layer].astype(jnp.float32), blocks)
    s = x.shape[0]
    h = _norm(x, p["ln1"], m)
    a = p["attn"]
    q = _mm(h, a["wq"], precision, "se,ehd->shd")
    k = _mm(h, a["wk"], precision, "se,ekd->skd")
    v = _mm(h, a["wv"], precision, "se,ekd->skd")
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, m), _rope(k, m)
    g = m["heads"] // m["kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = 1.0 / math.sqrt(m["head_dim"])
    blocks = max(s // Q_BLOCK, 1)
    qb = q.reshape(blocks, s // blocks, *q.shape[1:])

    def attend(args):
        i, qi = args
        rows = i * qi.shape[0] + jnp.arange(qi.shape[0])
        sc = _mm(qi, k, precision, "qhd,thd->hqt") * scale
        sc = jnp.where(jnp.arange(s)[None, None, :] <= rows[None, :, None],
                       sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm(pr, v, precision, "hqt,thd->qhd")

    o = jax.lax.map(attend, (jnp.arange(blocks), qb)).reshape(q.shape)
    x = x + _mm(o, a["wo"], precision, "shd,hde->se")
    h = _norm(x, p["ln2"], m)
    mp = p["mlp"]
    u = jax.nn.silu(_mm(h, mp["wg"], precision, "se,ef->sf")) \
        * _mm(h, mp["wi"], precision, "se,ef->sf")
    return x + _mm(u, mp["wo"], precision, "sf,fe->se")


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(ln_f, unembed, x, frozen, precision):
    m = dict(frozen)
    p = jax.tree.map(lambda w: w.astype(jnp.float32), ln_f)
    h = _q(_norm(x, p, m), precision)
    w_scale = jnp.max(jnp.abs(unembed)).astype(jnp.float32) / FP8_MAX
    cols = []
    for c in range(0, unembed.shape[1], VOCAB_BLOCK):
        w = unembed[:, c:c + VOCAB_BLOCK].astype(jnp.float32)
        if precision == "fp8":
            w = (w / w_scale).astype(jnp.float8_e4m3fn) \
                .astype(jnp.float32) * w_scale
        cols.append(jnp.einsum("se,ev->sv", h, w))
    return jnp.concatenate(cols, -1)


def bucket(n: int) -> int:
    return max(MIN_BUCKET, 1 << (n - 1).bit_length())


def hidden(weights, m: dict, seqs: list, precision: str = "f32") -> list:
    """Final hidden states ``(bucket, d)`` of each token sequence, padded
    to its bucket.  Runs layer by layer over every sequence, so one
    layer's float32 weights live at a time."""
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
        for layer in range(m["layers"]):
            i = jnp.int32(layer)
            xs = [_block(weights["blocks"], i, x, frozen, precision)
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


def teacher_rows(prompt_len: int, served: int) -> np.ndarray:
    """Positions whose next-token logits produced the served tokens: the
    last prompt position, then each served token but the last."""
    return np.arange(prompt_len - 1, prompt_len - 1 + served)


def served_gaps(ref_logits: np.ndarray, tokens) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit of
    the served token (>= 0)."""
    tokens = np.asarray(tokens, np.int64)
    best = ref_logits.max(-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def readings(ref_logits: list, tokens: list, tops: list) -> dict:
    """The numbers a run is judged by, over every sequence: ``max_gap``
    (``served_gaps``) and ``logit_err``, the widest distance between the
    logit each token was picked from (``tops``; NaN where it was not
    recorded) and the reference's logit of that token."""
    gap, err = 0.0, 0.0
    for ref, tok, top in zip(ref_logits, tokens, tops):
        tok = np.asarray(tok, np.int64)
        gap = max(gap, float(served_gaps(ref, tok).max(initial=0.0)))
        mine = ref[np.arange(len(tok)), tok]
        top = np.asarray(top, np.float64)
        known = ~np.isnan(top)
        if known.any():
            err = max(err, float(np.abs(top[known] - mine[known]).max()))
    return {"max_gap": gap, "logit_err": err}
