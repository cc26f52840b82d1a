"""Fused paged-attention decode kernel: equivalence sweep vs the gather
oracle and the contiguous slot-decode path, plus junk-page masking.

The kernel (kernels/paged_attention.py) is held to the gather-then-attend
oracle within the dtype's tolerance (2e-2 in bf16, 2e-5 in float32): it
rounds its unnormalised probabilities where the gather path rounds the
normalised ones, so the two agree to rounding, not bit for bit.  The
sweep crosses page size x pages-per-slot x GQA ratio x per-slot lengths
— freed slots whose page-table rows point at the reserved junk page 0,
lengths spanning several of the kernel's page blocks with a partial last
page and block, pages past a slot's length that would poison any read —
and checks three-way agreement: paged-Pallas == gather oracle ==
contiguous slot-decode attention over the same KV.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, strategies as st  # noqa: E402

from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import paged_attention  # noqa: E402
from repro.models.layers import dot_attention  # noqa: E402
from repro.serving.pool import page_rows  # noqa: E402


def make_case(seed, lens, page_size, max_pages, K, G, dh, dtype,
              poison=0.0):
    """A random page pool + *shuffled* page tables holding `lens` tokens
    per slot (0 = freed slot: zeroed page-table row).  `poison` fills the
    reserved junk page 0 so any read through it is loud."""
    slots = len(lens)
    held = [min(-(-L // page_size), max_pages) if L else 0 for L in lens]
    num_pages = sum(held) + 1
    # non-sequential page ids exercise the indirection, not just offsets
    order = np.random.default_rng(seed).permutation(
        np.arange(1, num_pages, dtype=np.int32))
    table = np.zeros((slots, max_pages), np.int32)
    i = 0
    for s_, h in enumerate(held):
        table[s_, :h] = order[i:i + h]
        i += h
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (slots, K * G, dh), jnp.float32).astype(dtype)
    kp = jax.random.normal(
        kk, (num_pages, page_size, K, dh), jnp.float32).astype(dtype)
    vp = jax.random.normal(
        kv, (num_pages, page_size, K, dh), jnp.float32).astype(dtype)
    if poison:
        kp = kp.at[0].set(poison)
        vp = vp.at[0].set(poison)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


CASES = [
    # (page_size, max_pages, K, G, dh, lens, dtype)
    (8, 4, 2, 2, 32, [32, 17, 8, 1], jnp.float32),
    (4, 4, 1, 4, 32, [16, 3, 0, 9], jnp.float32),        # MQA + freed slot
    (16, 2, 4, 1, 16, [32, 31, 30, 5], jnp.bfloat16),    # MHA, bf16 pool
    (8, 8, 2, 4, 64, [64, 1, 40, 0, 23], jnp.bfloat16),
    # 16-page blocks of 4 tokens: 150 tokens are blocks of 16, 16 and 6
    # pages, the last page half full; a zero-length slot between live ones
    (4, 40, 2, 2, 32, [150, 0, 97, 64], jnp.float32),
    (4, 40, 4, 1, 16, [97, 150, 1, 0], jnp.bfloat16),
]


@pytest.mark.parametrize("psize,mp,K,G,dh,lens,dtype", CASES)
def test_paged_kernel_three_way_equivalence(psize, mp, K, G, dh, lens,
                                            dtype):
    q, kp, vp, table, kv_len = make_case(7, lens, psize, mp, K, G, dh,
                                         dtype, poison=1e4)
    out = np.asarray(paged_attention(q, kp, vp, table, kv_len), np.float32)
    # gather oracle: masks junk pages, zeroes fully-masked rows — every
    # row comparable, freed slots included
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, want, rtol=_tol(dtype), atol=_tol(dtype))
    # contiguous slot decode: the same KV laid out (slots, t, K, dh),
    # attended with per-row lengths — live slots only (a fully-masked
    # contiguous row softmaxes to uniform, by design its output is
    # discarded upstream)
    t = mp * psize
    kc = jnp.take(kp, table, axis=0).reshape(len(lens), t, K, dh)
    vc = jnp.take(vp, table, axis=0).reshape(len(lens), t, K, dh)
    cont = dot_attention(q[:, None], kc, vc, causal=True,
                         q_offset=kv_len - 1, kv_len=kv_len)[:, 0]
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(out[live],
                               np.asarray(cont, np.float32)[live],
                               rtol=_tol(dtype), atol=_tol(dtype))


@settings(max_examples=8, deadline=None)
@given(psize=st.sampled_from([4, 8]),
       mp=st.sampled_from([2, 3, 4]),
       K=st.sampled_from([1, 2]),
       G=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 10_000),
       lens=st.lists(st.integers(0, 32), min_size=2, max_size=5))
def test_paged_kernel_hypothesis_sweep(psize, mp, K, G, seed, lens):
    lens = [min(L, psize * mp) for L in lens]
    if not any(lens):
        lens[0] = 1
    q, kp, vp, table, kv_len = make_case(seed, lens, psize, mp, K, G, 16,
                                         jnp.float32, poison=1e4)
    out = np.asarray(paged_attention(q, kp, vp, table, kv_len), np.float32)
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("psize,mp,lens", [(8, 4, [24, 13, 7]),
                                           (4, 40, [150, 130, 97])],
                         ids=["one-block", "blocks"])
def test_freed_slots_exact_zero_under_poisoned_junk(psize, mp, lens):
    """A freed/preempted slot (zeroed page-table row, stale nonzero
    kv_len — exactly what the decode step's `safe_pages` produces for
    inactive rows) must output exactly 0: the junk page is skipped
    in-kernel, never averaged in — and the slot after it, whose first
    block the freed slot's walk starts, is still read right."""
    q, kp, vp, table, kv_len = make_case(3, lens, psize, mp, 2, 2, 32,
                                         jnp.float32, poison=1e6)
    table = table.at[1].set(0)          # freed mid-flight; kv_len stays
    out = np.asarray(paged_attention(q, kp, vp, table, kv_len))
    assert np.all(out[1] == 0.0), "freed slot read the junk page"
    # the other slots are untouched by the free
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len))
    np.testing.assert_allclose(out[0], want[0], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[2], want[2], rtol=2e-5, atol=2e-5)


def test_junk_page_contents_never_leak_into_live_slots():
    """Live-slot outputs are bitwise independent of what rots in the
    reserved junk page (freed slots' dead decode writes land there)."""
    lens = [17, 9, 32]
    clean = make_case(11, lens, 8, 4, 2, 2, 32, jnp.float32, poison=0.0)
    dirty = make_case(11, lens, 8, 4, 2, 2, 32, jnp.float32, poison=1e6)
    out_clean = np.asarray(paged_attention(*clean[:3], clean[3], clean[4]))
    out_dirty = np.asarray(paged_attention(*dirty[:3], dirty[3], dirty[4]))
    np.testing.assert_array_equal(out_clean, out_dirty)


@pytest.mark.parametrize("psize,mp,K,G,dh,lens", [
    (8, 4, 2, 2, 32, [17, 9, 32]),
    (4, 40, 2, 2, 32, [150, 3, 0, 65]),
    (16, 24, 32, 1, 64, [300, 17]),             # stablelm's packed rows
], ids=["one-block", "blocks", "packed"])
def test_pages_past_the_length_are_never_read(psize, mp, K, G, dh, lens):
    """Every page-table entry past a slot's length points at a page of
    NaN: no such page is copied, so the output is finite, the oracle's on
    the clean table, and bitwise what the clean table gives."""
    q, kp, vp, clean, kv_len = make_case(13, lens, psize, mp, K, G, dh,
                                         jnp.float32)
    nan = kp.shape[0]
    kp = jnp.concatenate([kp, jnp.full_like(kp[:1], jnp.nan)])
    vp = jnp.concatenate([vp, jnp.full_like(vp[:1], jnp.nan)])
    held = -(-np.asarray(kv_len) // psize)
    past = np.arange(mp)[None, :] >= held[:, None]
    poisoned = jnp.where(jnp.asarray(past), nan, clean)
    kq, vq = _packed(kp), _packed(vp)
    out = np.asarray(paged_attention(q, kq, vq, poisoned, kv_len))
    assert np.isfinite(out).all()
    want = np.asarray(ref.paged_attention_ref(q, kp, vp, clean, kv_len))
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        out, np.asarray(paged_attention(q, kq, vq, clean, kv_len)))


def test_paged_kernel_rejects_bad_gqa():
    q = jnp.zeros((2, 3, 16))            # H=3 not divisible by K=2
    kp = jnp.zeros((4, 8, 2, 16))
    with pytest.raises(AssertionError):
        paged_attention(q, kp, kp, jnp.zeros((2, 2), jnp.int32),
                        jnp.zeros((2,), jnp.int32))


def _packed(pool):
    """The pool as the serving pool stores it: each token's (K, dh) as
    ``serving/pool.page_rows`` packs it (same bytes, same order)."""
    return pool.reshape(pool.shape[:-2] + page_rows(*pool.shape[-2:]))


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("psize,mp,K,G,dh,lens,dtype", [CASES[0], CASES[3]])
def test_paged_kernel_reads_one_layer_of_a_stacked_pool(layer, psize, mp, K,
                                                        G, dh, lens, dtype):
    """The decode step hands the kernel every layer's pool (layers,
    num_pages, page_size, rows, lanes) and a layer id: the output equals
    the oracle on that layer's pool alone, and the other layers' pages,
    NaN throughout, never reach it."""
    q, kp, vp, table, kv_len = make_case(7, lens, psize, mp, K, G, dh,
                                         dtype, poison=1e4)
    nan = jnp.full_like(kp, jnp.nan)
    k5 = jnp.stack([kp if i == layer else nan for i in range(3)])
    v5 = jnp.stack([vp if i == layer else nan for i in range(3)])
    out = np.asarray(paged_attention(q, _packed(k5), _packed(v5), table,
                                     kv_len, jnp.int32(layer)), np.float32)
    assert np.isfinite(out).all()
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, want, rtol=_tol(dtype), atol=_tol(dtype))
    # and bitwise what the one-layer call gives
    np.testing.assert_array_equal(
        out, np.asarray(paged_attention(q, _packed(kp), _packed(vp), table,
                                        kv_len), np.float32))


@pytest.mark.parametrize("K,G,dh", [(2, 4, 64), (4, 2, 32), (8, 1, 32),
                                    (8, 2, 16)],
                         ids=["r2", "r4", "r4x2rows", "r8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_packed_rows(K, G, dh, dtype):
    """Heads packed several to a 128-lane row (head_dim under 128, as the
    serving pool stores them) give the oracle's output on the unpacked
    pool, and the unpacked kernel's to rounding — freed slots and a
    poisoned junk page included."""
    assert page_rows(K, dh)[1] == 128
    lens = [40, 7, 0, 33]
    q, kp, vp, table, kv_len = make_case(5, lens, 8, 6, K, G, dh, dtype,
                                         poison=1e4)
    out = np.asarray(paged_attention(q, _packed(kp), _packed(vp), table,
                                     kv_len), np.float32)
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, want, rtol=_tol(dtype), atol=_tol(dtype))
    # the two layouts sum in different orders: equal to the output's
    # rounding (float32: 1e-5; bfloat16: its epsilon)
    tol = max(1e-5, float(jnp.finfo(dtype).eps))
    flat = np.asarray(paged_attention(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, flat, rtol=tol, atol=tol)
    assert np.all(out[2] == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_packed_rows_over_blocks(dtype):
    """stablelm-2-1.6b's pool layout: 32 kv heads of 64 packed to 16 rows
    of 128 lanes, pages of 16 tokens, so a block is 16 pages: a slot of
    600 tokens spans three blocks (the last one partial, its last page
    half full) beside a freed slot and short ones."""
    K, dh = 32, 64
    assert page_rows(K, dh) == (16, 128)
    lens = [600, 33, 0, 257]
    q, kp, vp, table, kv_len = make_case(21, lens, 16, 40, K, 1, dh, dtype,
                                         poison=1e4)
    out = np.asarray(paged_attention(q, _packed(kp), _packed(vp), table,
                                     kv_len), np.float32)
    want = np.asarray(
        ref.paged_attention_ref(q, kp, vp, table, kv_len), np.float32)
    np.testing.assert_allclose(out, want, rtol=_tol(dtype), atol=_tol(dtype))
    assert np.all(out[2] == 0.0)
