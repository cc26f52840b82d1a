"""The float32 reference against the program at smoke size on the CPU:
chunked prefill into the paged pool, then paged decode, compared on the
logits at every served position.  The float8 control of the same
reference fails the same comparison."""

from __future__ import annotations

from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights as weights_mod
from bench.drivers import serve as serve_driver
from bench.tests import smoke

SEED = 2**31 + 5
PROMPTS = (37, 9, 70)
NEW = 12
# the program computes in bfloat16: its logits stay within this share of
# the reference's logit spread (measured 0.024 to 0.068 at these sizes;
# the float8 control measured 0.26 to 0.96)
PROGRAM_TOL = 0.12


def served_logits(config: dict):
    """Serve PROMPTS greedily through the engine's chunk and decode steps
    and collect the logits each served token was picked from."""
    import repro.serving.engine as engine_mod
    from repro.serving.scheduler import Request, Scheduler, _Entry

    m = reference.model_dims(config["config"])
    arch = serve_driver.program_arch(config)
    made = []

    def bench_weights(table, _rng):
        w = weights_mod.make_weights(m, SEED)
        weights_mod.check_layout(w, table)
        made.append(w)
        return w

    with mock.patch.object(engine_mod, "init_params", bench_weights):
        eng = engine_mod.ServeEngine(arch=arch, num_slots=3, max_len=96,
                                     kv_layout="paged", page_size=8,
                                     num_pages=40, log=lambda *_: None)
    pool = eng.make_pool()
    got: dict = {}
    rid_of_slot: dict = {}

    def chunk_fn(cache, tokens, slot, offset, n_valid, *extras):
        logits, new = eng.chunk_fn(cache, tokens, slot, offset, n_valid,
                                   *extras)
        rid = rid_of_slot[int(slot)]
        if int(offset) + int(n_valid) == PROMPTS[rid]:
            got.setdefault(rid, []).append(np.asarray(logits[0, -1],
                                                      np.float32))
        return logits, new

    def decode_fn(cache, tokens, active, *extras):
        logits, new = eng.decode_fn(cache, tokens, active, *extras)
        for slot in sched.active:
            got[rid_of_slot[slot]].append(np.asarray(logits[slot, -1],
                                                     np.float32))
        return logits, new

    sched = Scheduler(pool, eng.prefill_fn, decode_fn,
                      chunk_step_fn=chunk_fn, prefill_chunk=16,
                      vocab_size=m["vocab"])
    sched.all_greedy = True
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, m["vocab"], n,
                                               dtype=np.int32),
                    max_new_tokens=NEW) for i, n in enumerate(PROMPTS)]
    orig_alloc = pool.alloc

    def alloc():
        slot = orig_alloc()
        rid_of_slot[slot] = len(rid_of_slot) if slot not in rid_of_slot \
            else rid_of_slot[slot]
        return slot

    pool.alloc = alloc
    for r in reqs:
        sched.queue.append(_Entry(r))
    while sched.queue or sched.active or sched.prefill_backlog:
        sched.admit_from_queue()
        sched.step()
    tokens = {st.rid: st.tokens for st in sched.done}
    return m, made[0], reqs, tokens, got


@pytest.fixture(scope="module", params=[smoke.DEEPSEEK, smoke.STABLELM2],
                ids=["deepseek-7b-smoke", "stablelm-2-smoke"])
def served(request):
    return request.param, served_logits(request.param)


def _ref(m, w, reqs, tokens, precision):
    seqs = [np.concatenate([r.prompt, np.asarray(tokens[r.rid][:-1],
                                                  np.int32)]) for r in reqs]
    rows = [reference.teacher_rows(len(r.prompt), NEW) for r in reqs]
    return reference.logits_at(w, m, seqs, rows, precision)


def test_program_matches_reference_on_logits(served):
    _, (m, w, reqs, tokens, got) = served
    ref = _ref(m, w, reqs, tokens, "f32")
    for r, want in zip(reqs, ref):
        prog = np.stack(got[r.rid])
        assert prog.shape == want.shape == (NEW, m["vocab"])
        err = np.max(np.abs(prog - want)) / np.std(want)
        assert err < PROGRAM_TOL, (r.rid, err)
        gaps = reference.served_gaps(want, tokens[r.rid])
        assert np.all(gaps >= 0) and gaps.max() < PROGRAM_TOL * np.std(want)


def test_lower_precision_control_fails(served):
    _, (m, w, reqs, tokens, got) = served
    ref = _ref(m, w, reqs, tokens, "f32")
    low = _ref(m, w, reqs, tokens, "fp8")
    errs = [np.max(np.abs(lo - want)) / np.std(want)
            for lo, want in zip(low, ref)]
    assert max(errs) > PROGRAM_TOL, errs


def test_reference_pieces():
    x = jnp.arange(2 * 3 * 8, dtype=jnp.float32).reshape(2, 3, 8) / 10
    m = {"rope_fraction": 0.5, "rope_theta": 10000.0}
    y = reference._rope(x, m)
    # position 0 is not rotated, the unrotated half is untouched
    np.testing.assert_allclose(y[0], x[0], rtol=1e-6)
    np.testing.assert_allclose(y[:, :, 4:], x[:, :, 4:])
    # a rotation keeps the norm of each rotated pair
    np.testing.assert_allclose(jnp.linalg.norm(y[1, :, :4], axis=-1),
                               jnp.linalg.norm(x[1, :, :4], axis=-1),
                               rtol=1e-5)
    assert reference.bucket(3) == reference.MIN_BUCKET
    assert reference.bucket(1000) == 1024
    np.testing.assert_array_equal(reference.teacher_rows(5, 3), [4, 5, 6])
    np.testing.assert_allclose(
        reference.served_gaps(np.array([[1.0, 3.0], [2.0, 0.5]]), [0, 0]),
        [2.0, 0.0])
