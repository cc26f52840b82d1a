"""Per-request sampling for the slot-wise decode loop.

Each pool slot samples with its *own* temperature / top-k / top-p / PRNG
stream: the key for a draw is ``fold_in(fold_in(base, rid), step)`` where
``step`` is how many tokens the request has generated so far.  Keying on
the request id and the generation step (rather than the slot or the wall
clock) makes sampling deterministic across admission order, slot
assignment, *and* preemption — a request that is preempted and later
resumed re-draws exactly the token stream it would have produced
uninterrupted, which is what keeps the preemption equivalence tests
honest under page pressure.  It is also what makes speculative
decoding bit-identical: the verify step samples positions
``step .. step+k`` with the very same per-row math, so an accepted burst
reproduces the sequential draws token for token.

Greedy decoding is the ``temperature == 0`` row-wise special case, so a
trace of default requests reproduces the old argmax scheduler bit-for-bit.
Top-k is capped at ``effective_top_k`` (one static ``lax.top_k``; per-row
k masks below the row's k-th value); ``top_k == 0`` disables the filter.
Requests asking for ``top_k > K_CAP`` are rejected at submission
(``Scheduler.validate``) instead of being silently clamped here, and the
effective per-request k (after the vocab cap) is surfaced in
``ServeStats.effective_top_k``.  Top-p (nucleus) keeps the smallest
probability-sorted set whose mass reaches p; ``top_p >= 1`` leaves the
logits bit-untouched, so default requests are unaffected.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

K_CAP = 64


def effective_top_k(top_k: int, vocab_size: int, k_cap: int = K_CAP) -> int:
    """The k the sampler actually applies for a request's ``top_k``:
    0 (filter off) or min(top_k, K_CAP, vocab)."""
    if top_k <= 0:
        return 0
    return min(top_k, k_cap, vocab_size)


def make_sampler(seed: int, k_cap: int = K_CAP):
    """Jitted (logits, temperature, top_k, top_p, rids, steps) -> int32.

    logits: (rows, vocab); temperature/top_p float32 (rows,);
    top_k/rids/steps int32 (rows,).  Works for the full pool
    (rows = num_slots), the single-row prefill first-token draw, and the
    flattened (num_slots * (k+1)) speculative verify batch alike.
    """
    base = jax.random.PRNGKey(seed)

    def _row(lg, temp, k, p, rid, step):
        lg = lg.astype(jnp.float32)
        greedy = jnp.argmax(lg).astype(jnp.int32)
        key = jax.random.fold_in(jax.random.fold_in(base, rid), step)
        cap = min(k_cap, lg.shape[-1])   # static: top_k(v, 64) on vocab 4
        kk = jnp.clip(k, 0, cap)
        vals, _ = jax.lax.top_k(lg, cap)
        kth = vals[jnp.maximum(kk - 1, 0)]
        masked = jnp.where((kk > 0) & (lg < kth), -jnp.inf, lg)
        # nucleus (top-p) on the already-k-filtered logits: keep the
        # smallest probability-sorted set whose mass reaches p (ties at
        # the cutoff all kept — deterministic).  Gated with a select so
        # p >= 1 (the default) passes `masked` through bit-identically.
        probs = jax.nn.softmax(masked)
        sp = jnp.sort(probs)[::-1]
        prior = jnp.cumsum(sp) - sp          # mass strictly above each tok
        cut = jnp.min(jnp.where(prior < p, sp, jnp.inf))
        nucleus = jnp.where(probs >= cut, masked, -jnp.inf)
        masked = jnp.where((p > 0) & (p < 1), nucleus, masked)
        drawn = jax.random.categorical(
            key, masked / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        # top_k == 1 IS argmax; routing it through categorical would break
        # logit ties randomly where argmax breaks them by index
        return jnp.where((temp > 0) & (kk != 1), drawn, greedy)

    @jax.jit
    def sample(logits, temperature, top_k, top_p, rids, steps):
        return jax.vmap(_row)(logits, temperature, top_k, top_p, rids, steps)

    return sample
