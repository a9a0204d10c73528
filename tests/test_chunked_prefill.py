"""Chunked prefill parity: segmented prompt ingestion must be invisible.

`backbone.prefill_chunk` feeds a prompt to the model in fixed token
segments; the claim is that the logits and every written K/V cache
element agree with a one-shot prefill of the same prompt within
float32 reassociation (`agreement.miss`), for any segmentation, and that
a misplaced or lost segment misses by far more.  On top of that, the
scheduler's staged-admission path (long buckets prefill between decode
chunks) must produce the same greedy tokens as one-shot admission and
per-request decode.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agreement import FAULT_MARGIN, miss
from repro.configs import get_config
from repro.models import backbone as bb
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

KEY = jax.random.PRNGKey(0)
# float32 reassociation through the reduced model: segments and one shot
# sum the same attention terms in other orders (measured: 5.2e-7 on logits)
PREFILL_RTOL = 1e-5
PREFILL_ATOL_FRAC = 1e-5      # of max|one-shot value|


@pytest.fixture(scope="module")
def system():
    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, KEY)
    return cfg, params


def _prefill_miss(cfg, params, T, W, seg, fault=None) -> float:
    """How far an N-segment prefill of a random T-token prompt in a
    W-wide bucket lands from the one-shot prefill, in tolerances: the
    largest `miss` over the last-token logits and the K and V rows the
    prompt wrote.  fault: "slot_off" writes the second segment one slot
    late; "stale_key" loses the first segment's write, so the keys a
    previous occupant left there stay attendable."""
    rng = np.random.RandomState(T * 100 + seg)
    tokens = rng.randint(0, cfg.vocab, (1, T)).astype(np.int32)

    padded = np.zeros((1, W), np.int32)
    padded[:, :T] = tokens
    oneshot = jax.jit(partial(bb.prefill, cfg), static_argnames=("max_len",))
    logits1, cache1, _ = oneshot(
        params, {"tokens": jnp.asarray(padded)},
        lengths=jnp.asarray([T], jnp.int32), max_len=W)

    n_segs = -(-W // seg)
    chunk = jax.jit(partial(bb.prefill_chunk, cfg),
                    static_argnames=("attend_width",))
    cache2 = bb.init_cache(cfg, 1, n_segs * seg)
    if fault == "stale_key":
        cache2 = {n: jax.random.normal(jax.random.fold_in(KEY, i), c.shape,
                                       c.dtype)
                  for i, (n, c) in enumerate(sorted(cache2.items()))}
    seg_toks = np.zeros((1, n_segs * seg), np.int32)
    seg_toks[:, :T] = tokens
    logits2 = None
    for d in range(0, W, seg):
        last = min(max(T - 1 - d, 0), seg - 1)
        at = d + 1 if fault == "slot_off" and d == seg else d
        lg, written = chunk(params, jnp.asarray(seg_toks[:, d:d + seg]),
                            cache2, jnp.int32(at), attend_width=W,
                            last_index=jnp.int32(last))
        if not (fault == "stale_key" and d == 0):
            cache2 = written
        if d <= T - 1 < d + seg:
            logits2 = lg
        if d + seg >= T:
            break

    tol = dict(rtol=PREFILL_RTOL, atol_frac=PREFILL_ATOL_FRAC)
    return max([miss(logits2, logits1, **tol)]
               + [miss(np.asarray(cache2[nm])[:, :, :, :T],
                       np.asarray(cache1[nm])[:, :, :, :T], **tol)
                  for nm in ("k", "v")])


@pytest.mark.parametrize("T,W,seg", [
    (48, 48, 16),      # even segments, no bucket padding
    (41, 48, 16),      # ragged prompt in a padded bucket
    (48, 48, 48),      # degenerate: one segment
    (33, 64, 8),       # many small segments
])
def test_chunked_prefill_agrees_with_oneshot(system, T, W, seg):
    """N-segment prefill == one-shot prefill within the written
    tolerance, in both the last-token logits and every written cache
    element."""
    cfg, params = system
    assert _prefill_miss(cfg, params, T, W, seg) <= 1


@pytest.mark.parametrize("fault", ["slot_off", "stale_key"])
def test_chunked_prefill_tolerance_catches_fault(system, fault):
    """A segment written one slot off, or a stale key left attendable,
    misses the one-shot prefill by FAULT_MARGIN tolerances or more."""
    cfg, params = system
    assert _prefill_miss(cfg, params, 41, 48, 16, fault) >= FAULT_MARGIN


def test_scheduler_chunked_admission_matches_reference(system):
    """Long prompts admitted through staged (segmented) prefill decode to
    exactly the per-request reference tokens, mixed with short traffic."""
    cfg, params = system
    eng = ServeEngine(cfg, params, max_len=192)   # reference path
    sched_eng = ServeEngine(
        cfg, params, max_len=192,
        scheduler=SchedulerConfig(buckets=(8, 16, 32, 64, 128),
                                  max_slots=4, prefill_group=2, chunk=4,
                                  prefill_segment=32))
    rng = np.random.RandomState(7)
    lens = [100, 8, 16, 97, 8, 128, 16]           # 3 chunked admissions
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=5)
            for L in lens]
    outs = sched_eng.generate(reqs)
    assert len(outs) == len(reqs)
    for req, got in zip(reqs, outs):
        np.testing.assert_array_equal(got.tokens,
                                      eng.generate([req])[0].tokens)


def test_scheduler_chunked_vs_oneshot_admission(system):
    """The same long-prompt queue with chunked prefill on and off
    completes with identical greedy tokens."""
    cfg, params = system
    rng = np.random.RandomState(8)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in (100, 8, 120, 16)]

    def tokens_with(segment):
        sched = ContinuousScheduler(
            cfg, params, max_len=192,
            sched=SchedulerConfig(buckets=(8, 16, 32, 64, 128),
                                  max_slots=4, prefill_group=2, chunk=4,
                                  prefill_segment=segment))
        rids = [sched.submit(r) for r in reqs]
        outs = sched.run()
        return [outs[r].tokens for r in rids]

    for a, b in zip(tokens_with(32), tokens_with(0)):
        np.testing.assert_array_equal(a, b)


def test_staged_admission_never_stalls_decode(system):
    """While a long prompt stages, short requests keep decoding: the
    scheduler interleaves one prefill segment per round, so the short
    request completes before the long admission finishes staging."""
    cfg, params = system
    sched = ContinuousScheduler(
        cfg, params, max_len=192,
        sched=SchedulerConfig(buckets=(8, 16, 32, 64, 128), max_slots=2,
                              prefill_group=1, chunk=2, prefill_segment=16))
    long_rid = sched.submit(Request(
        tokens=np.arange(128) % cfg.vocab, max_new_tokens=3))
    short_rid = sched.submit(Request(
        tokens=np.arange(8) % cfg.vocab, max_new_tokens=3))
    finished = []
    for _ in range(64):
        finished.extend(sched.step())
        if long_rid in finished:
            break
    assert short_rid in finished and long_rid in finished
    assert finished.index(short_rid) < finished.index(long_rid)
