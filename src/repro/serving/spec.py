"""Draft-then-verify speculative decoding: the drafter side.

The serving decode loop emits one token per jitted step per slot, so
tokens/step — the bench's gated metric — is hard-capped by batch
occupancy.  Speculative decoding breaks the cap: a cheap **drafter**
proposes k tokens per slot from the slot's own history, one VERIFY step
(``training/steps.build_verify_step_slots``) scores all k+1 positions
against pool KV at once, and the scheduler accepts the longest
prefix of drafts that matches what the per-``(rid, step)`` sampler would
have drawn sequentially — so speculative streams are **bit-identical** to
non-speculative ones, and a verify step that accepts a tokens advances
the request by a+1 for the price of one jitted call.

The drafter here is deliberately model-free: ``NGramDrafter`` predicts by
longest-suffix n-gram lookup over the request's prompt + generated tokens
(prompt-copy falls out of the same rule, since the prompt is part of the
history).  Any object with ``draft(history, k) -> list[int]`` plugs into
``Scheduler(drafter=...)`` / ``ServeEngine(drafter=...)`` — the hook a
small ``configs/`` model drops into later (its drafter would run its own
tiny decode loop over ``history`` and return k greedy tokens; everything
downstream — verify, acceptance, page charging — is drafter-agnostic,
because a *wrong* draft costs only its rejected KV write, which the next
step overwrites before any causal mask admits it).
"""

from __future__ import annotations


class Drafter:
    """Protocol: propose k tokens likely to follow ``history``.

    ``history`` is the request's full token prefix — prompt plus every
    emitted token, including the pending one not yet in KV — and the
    return value is exactly ``k`` proposed continuation tokens.  Drafts
    never affect correctness (a mismatch just ends the accepted burst),
    only the accepted-tokens/verify-step ratio.
    """

    def draft(self, history: list[int], k: int) -> list[int]:
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Longest-suffix n-gram drafter over the request's own history.

    For each proposed token: take the history's last-n suffix for
    n = max_n..1, find that n-gram's most recent earlier occurrence, and
    propose the token that followed it; if no suffix recurs, repeat the
    last token.  The proposal is appended to a working copy of the
    history, so one call drafts a k-token continuation, not k independent
    guesses.  On repetitive streams (the bench's small-vocab trace, or
    any prompt-echoing workload) the longest-suffix rule locks onto the
    cycle and whole bursts verify.
    """

    def __init__(self, max_n: int = 3):
        if max_n < 1:
            raise ValueError(f"max_n {max_n} < 1")
        self.max_n = max_n
        # observability: how often the suffix rule actually fires vs the
        # repeat-last fallback — a drafter whose fallback dominates is
        # wasting verify steps, which is the tuner's cue to turn spec off
        self.calls = 0                # draft() invocations
        self.drafted_tokens = 0       # k summed over calls
        self.ngram_hits = 0           # proposals from a recurring suffix
        self.fallbacks = 0            # proposals from repeat-last

    def _next(self, hist: list[int]) -> int:
        L = len(hist)
        for n in range(min(self.max_n, L - 1), 0, -1):
            suffix = hist[L - n:]
            # most recent earlier occurrence of the suffix n-gram
            for p in range(L - n - 1, -1, -1):
                if hist[p:p + n] == suffix:
                    self.ngram_hits += 1
                    return hist[p + n]
        self.fallbacks += 1
        return hist[-1]

    def draft(self, history: list[int], k: int) -> list[int]:
        self.calls += 1
        self.drafted_tokens += k
        hist = [int(t) for t in history]
        if not hist:
            return [0] * k
        out = []
        for _ in range(k):
            t = self._next(hist)
            out.append(t)
            hist.append(t)
        return out
