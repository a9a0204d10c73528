"""Serving engine: request queue -> bucketed prefill -> slot-pool decode.

Two execution paths share one `generate` API for the Remote-NN role:

  * equal-length fast path — requests whose prompts share one length are
    grouped into a single prefill and decoded as one
    `jax.lax.while_loop` device program (sampling, EOS/done masking and
    per-request length limits in-graph), issuing
    O(1) host transfers per call.  Bit-compatible with the PR-1 engine.
  * continuous batching — mixed-length queues route through
    `repro.serve.scheduler.ContinuousScheduler`: prompts are right-padded
    into length buckets (pad keys masked out of attention), prefilled
    per bucket, and injected into a fixed-width decode slot pool whose
    chunked while_loop segments evict finished requests and admit queued
    ones without recompiling.  Greedy outputs are identical to decoding
    each request alone.

Per-request temperature (0 => greedy) and EOS ids are honoured in-graph
on both paths; architectures the scheduler cannot serve (recurrent state,
sliding windows, absolute positions) fall back to equal-length grouping.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import backbone as bb
from repro.serve.scheduler import (
    ContinuousScheduler,
    SchedulerConfig,
    sample_tokens,
    supports_continuous_batching,
)


@dataclasses.dataclass
class Request:
    tokens: np.ndarray                 # (T,) prompt
    max_new_tokens: int = 16
    eos_id: int = -1                   # -1: never stops early
    temperature: float = 0.0           # 0 => greedy
    extras: Optional[dict] = None      # patches / frames for vlm / audio
    deadline_s: Optional[float] = None  # wall seconds from submit; past it
                                        # the scheduler evicts the request
                                        # between chunks (partial tokens,
                                        # Completion.timed_out=True)


@dataclasses.dataclass
class Completion:
    tokens: np.ndarray
    steps: int
    timed_out: bool = False            # deadline-evicted mid-decode: tokens
                                       # hold whatever was generated in time


def _decode_loop(cfg: ArchConfig, params, logits0, cache, cache_len, key,
                 eos_ids, max_lens, max_new, temps, *, buf_len: int,
                 greedy: bool):
    """Whole decode phase as one device program.

    Samples the first token from the prefill logits, then runs a
    while_loop of decode_step + sample + done-masking.  max_new is a
    traced loop bound (no recompile across request budgets); temps is a
    per-request vector (rows with temp <= 0 take argmax in-graph); only
    the batch/cache shapes and the all-greedy flag shape the program.
    Returns (token buffer (B, buf_len), per-request lengths, steps
    executed).
    """
    B = logits0.shape[0]

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), key
        key, sub = jax.random.split(key)
        return sample_tokens(logits, temps, sub), key

    tok0, key = sample(logits0, key)
    buf = jnp.zeros((B, buf_len), jnp.int32).at[:, 0].set(tok0)
    lengths = jnp.ones((B,), jnp.int32)
    done = (tok0 == eos_ids) | (lengths >= max_lens)
    state = (jnp.zeros((), jnp.int32), buf, lengths, done, tok0[:, None],
             cache, jnp.asarray(cache_len, jnp.int32), key)

    def cond(state):
        step, _, _, done, _, _, _, _ = state
        return (step < max_new - 1) & ~jnp.all(done)

    def body(state):
        step, buf, lengths, done, tok, cache, cl, key = state
        logits, cache = bb.decode_step(cfg, params, tok, cache, cl)
        t, key = sample(logits, key)
        active = ~done
        pos = jnp.where(active, lengths, buf_len)      # OOB rows -> dropped
        buf = buf.at[jnp.arange(B), pos].set(t, mode="drop")
        lengths = lengths + active.astype(jnp.int32)
        done = done | (active & ((t == eos_ids) | (lengths >= max_lens)))
        return (step + 1, buf, lengths, done, t[:, None], cache, cl + 1, key)

    step, buf, lengths, done, _, _, _, _ = jax.lax.while_loop(cond, body, state)
    return buf, lengths, step + 1


def _stack_extras(requests: list[Request]) -> dict:
    """Validated extras batch: every request must carry the same keys
    (a mixed batch would silently drop or misalign modality inputs)."""
    key_sets = {frozenset((r.extras or {}).keys()) for r in requests}
    if len(key_sets) > 1:
        raise ValueError(
            "all requests in a batch must carry the same extras keys; got "
            + " vs ".join(str(sorted(s)) for s in key_sets))
    ex = requests[0].extras or {}
    return {k: jnp.asarray(np.stack([r.extras[k] for r in requests]))
            for k in ex}


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_len: int = 256,
                 seed: int = 0, scheduler: Optional[SchedulerConfig] = None,
                 mesh=None, telemetry=None):
        from repro.serve import telemetry as _telemetry
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.tel = telemetry if telemetry is not None else _telemetry.default()
        self.mesh = mesh            # scheduler path only: slot pool shards
                                    # over the data axes, params go tensor-
                                    # parallel (launch.partition)
        if mesh is not None:
            assert supports_continuous_batching(cfg), \
                f"{cfg.name}: sharded serving runs through the continuous " \
                "scheduler, which this architecture gates out — a meshed " \
                "engine would silently serve unsharded on one device"
        self._seed = seed
        self._key = jax.random.PRNGKey(seed)
        self._sched_cfg = scheduler or SchedulerConfig()
        self._sched: Optional[ContinuousScheduler] = None
        # the prefill cache is not donated: the loop returns no cache, so
        # no output could take its buffers (donation would only warn)
        self._loop = jax.jit(partial(_decode_loop, cfg),
                             static_argnames=("buf_len", "greedy"))
        # jit'd prefill (compiles once per prompt length): the op-by-op
        # eager prefill used to dominate the equal-length path's wall
        # clock, benching it below the scheduler on the same requests
        self._prefill = jax.jit(partial(bb.prefill, cfg),
                                static_argnames=("max_len",))

    @property
    def scheduler(self) -> ContinuousScheduler:
        """The lazily built continuous-batching scheduler (shared pool and
        compiled programs across generate calls)."""
        if self._sched is None:
            self._sched = ContinuousScheduler(
                self.cfg, self.params, sched=self._sched_cfg,
                max_len=self.max_len, seed=self._seed + 1, mesh=self.mesh,
                telemetry=self.tel)
        return self._sched

    def generate(self, requests: list[Request]) -> list[Completion]:
        """One Completion per request, in submission order.  Equal-length
        prompts take the single-batch fast path (unless a mesh is set —
        sharded serving always goes through the scheduler); mixed lengths
        run through the continuous-batching scheduler (or equal-length
        grouping when the architecture rules the scheduler out)."""
        assert requests, "empty batch"
        lens = {len(r.tokens) for r in requests}
        schedulable = (supports_continuous_batching(self.cfg)
                       and all(r.extras is None for r in requests))
        deadlines = any(r.deadline_s is not None for r in requests)
        if deadlines and not schedulable:
            raise ValueError(
                "per-request deadlines are honored by the continuous "
                "scheduler only; this architecture (or extras-carrying "
                "batch) routes through the equal-length path, which cannot "
                "evict mid-decode")
        # with a mesh, everything routes through the (sharded) scheduler:
        # the fast path is single-device, and silently dropping the mesh
        # would un-shard params a caller sharded because they must be
        if self.mesh is not None and not schedulable:
            raise ValueError(
                "sharded serving cannot take requests with extras — they "
                "route through the single-device fast path, dropping the "
                "mesh")
        if len(lens) == 1 and self.mesh is None and not deadlines:
            return self._generate_equal(requests)
        if schedulable:
            sched = self.scheduler
            rids = [sched.submit(r) for r in requests]
            outs = sched.run()
            return [outs[rid] for rid in rids]
        # fallback: one fast-path call per prompt-length group
        by_len: dict[int, list[int]] = {}
        for i, r in enumerate(requests):
            by_len.setdefault(len(r.tokens), []).append(i)
        out: list[Optional[Completion]] = [None] * len(requests)
        for idxs in by_len.values():
            for i, c in zip(idxs, self._generate_equal(
                    [requests[i] for i in idxs])):
                out[i] = c
        return out

    def _generate_equal(self, requests: list[Request]) -> list[Completion]:
        """Single-prefill path: all prompts share one length."""
        T = len(requests[0].tokens)
        assert all(len(r.tokens) == T for r in requests)
        batch = {"tokens": jnp.asarray(
            np.stack([r.tokens for r in requests]), jnp.int32)}
        batch.update(_stack_extras(requests))

        logits, cache, total_T = self._prefill(self.params, batch,
                                               max_len=self.max_len)
        if self.tel.enabled:
            self.tel.note_compiles("engine.prefill", self._prefill,
                                   shape=f"T{T}xB{len(requests)}")
        total_T = int(total_T)
        max_new = max(r.max_new_tokens for r in requests)
        assert max_new <= self.max_len, \
            f"max_new_tokens {max_new} exceeds engine max_len {self.max_len}"
        if self.cfg.sliding_window == 0:
            # full-attention caches are not rings: a wrap would overwrite
            # context the model still attends to, silently (SWA archs wrap
            # by design — the window is the attention span)
            assert total_T + max_new <= self.max_len, \
                f"context {total_T} + max_new_tokens {max_new} exceeds " \
                f"engine max_len {self.max_len}: decode would ring-wrap " \
                "over live context"
        temps = np.asarray([r.temperature for r in requests], np.float32)
        self._key, sub = jax.random.split(self._key)
        eos_ids = jnp.asarray([r.eos_id for r in requests], jnp.int32)
        max_lens = jnp.asarray([r.max_new_tokens for r in requests], jnp.int32)

        buf, lengths, steps = self._loop(
            self.params, logits, cache, total_T, sub, eos_ids, max_lens,
            jnp.int32(max_new), jnp.asarray(temps),
            buf_len=self.max_len, greedy=bool(np.all(temps <= 0.0)))
        if self.tel.enabled:
            self.tel.note_compiles(
                "engine.decode_loop", self._loop,
                shape=f"buf{self.max_len}_greedy{bool(np.all(temps <= 0.0))}")
        # the single device->host transfer of the decode phase
        buf, lengths, steps = (np.asarray(buf), np.asarray(lengths),
                               int(steps))
        return [Completion(buf[b, :lengths[b]].astype(np.int32), steps)
                for b in range(len(requests))]
