"""Continuous-batching scheduler: bucketed prefill + paged slot-pool decode.

The unit of work is a `Request` (see `repro.serve.engine`).  Admission
right-pads each prompt to the smallest configured length bucket, runs one
prefill per group (compiled once per bucket), and *injects* the resulting
rows into free slots of a fixed-width decode pool.  Decoding runs in
chunked `lax.while_loop` segments over the whole pool: per-slot EOS ids,
token budgets, and sampling temperatures all live in-graph, so one
compiled program serves every mix of requests.  Between segments the host
*evicts* finished slots (one small device->host copy of the token buffer)
and admits queued requests into the freed slots — the loop never
recompiles and never drains.

The slot-pool KV cache is *page granular*: prefill returns rows at the
bucket's page-rounded width (`page_size`) instead of the full pool width,
so injecting a request copies only the pages its prompt covers — slots
keep whatever stale keys the previous occupant left past that point, and
decode masks them out by depth (a cache slot only becomes attendable the
step its row writes it).  The decode chunk donates the pool and
`decode_step` carries it through its layer loop, writing each row's new
K/V token in place, so a step moves one token per row and layer, never
the pool.  Attention reads only the KV pages below each row's depth
(`repro.kernels.decode_attention`), so its cost follows the pool's
occupancy; the rest of the step (weights, projections, FFN, readout)
runs every slot whatever its occupancy.

Long prompts admit through *chunked prefill*: a prompt whose bucket
exceeds `prefill_segment` is staged one segment at a time between decode
chunks (`backbone.prefill_chunk`, which agrees with one-shot prefill
within float32 reassociation), so a long admission can never stall the decode pool for more than one
segment of prefill work.  One admission stages at a time; short groups
keep admitting around it, and the staged slot joins the pool when its
last segment lands.

The pool is *mesh-shardable*: given a ``mesh`` (see
`launch.mesh.make_serving_mesh`), the slot axis of every pool leaf shards
over the data axes via `NamedSharding` (`launch.partition.pool_shardings`)
and params go tensor-parallel through the serving partition rules — the
same compiled programs run SPMD across the mesh, host-side evict/inject
addresses slots whose rows live wholly on one data shard, and greedy
outputs stay bit-identical to the single-device pool (tested on a forced
multi-device CPU mesh).

With ``SchedulerConfig.overlap`` (the default) the host pipelines itself
one round deep against the device: while round k-1's decode chunk is
still in flight, round k's staged prefill segment dispatches and its
admission groups are bucketed/tokenized and injected — no host sync
between them, JAX async dispatch queues it all behind the chunk.  Only
then does the host block on round k-1's done flags (whose device->host
copy started at dispatch, so the read usually lands instantly), evict,
admit into the freed slots, and dispatch round k's chunk.  A long
admission's prefill segments therefore overlap decode instead of taking
turns with it, and the device never idles while the host tokenizes.
Evict/admit timing is round-identical to ``overlap=False`` (admit,
decode, block on the drain every round — the A/B baseline); completions
just report one round later.

Correctness invariants (tested against one-request-at-a-time decode):
  * pad keys are masked out of prefill attention and pad/stale cache
    slots are overwritten by decode writes before they become
    attendable, so neither bucket padding nor page-granular injects can
    change a request's tokens;
  * batch rows are independent end-to-end, so evict/inject of one slot
    preserves every other slot's cache contents bit-for-bit — which is
    also why overlap's one-round-late eviction cannot move a token: a
    done row is masked out of decode in-graph until it is drained.

The padded-prefill path needs per-row attention masking and per-row cache
depths, so the scheduler serves attention-only token models (no recurrent
state to pollute with pads); `supports_continuous_batching` gates it and
`ServeEngine` falls back to equal-length grouping elsewhere.  MoE layers
serve through the dropless expert layer (`nn.moe.moe_serve`): no capacity
for pads to compete over, and pads and idle slots route nothing.  The
pool's cache is whatever token-axis leaves the model's attention keeps
(K and V, or latent attention's one latent row per token), moved by
leaf, never by name.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from contextlib import nullcontext
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels.common import round_up
from repro.models import backbone as bb
from repro.serve import telemetry as _telemetry

_NULL = nullcontext()     # reentrant: shared no-op for disabled telemetry


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    buckets: tuple[int, ...] = (8, 16, 32, 64, 128)
    max_slots: int = 8         # decode pool width (concurrent requests)
    prefill_group: int = 4     # fixed prefill batch (bounds compile count)
    chunk: int = 8             # decode steps per while_loop segment
    page_size: int = 32        # KV copy granularity: injects move
                               # ceil(bucket / page_size) pages, not the
                               # full pool-width strip
    prefill_segment: int = 64  # buckets above this prefill in segments of
                               # this many tokens, interleaved with decode
                               # chunks (0 disables chunked prefill)
    overlap: bool = True       # pipeline host scheduling against the
                               # in-flight decode chunk: drain one round
                               # behind, prepare admissions while the
                               # device runs (False: serialized rounds)
    prefix_cache: bool = False  # share prompt-prefix KV pages across
                                # admissions (serve.prefix_cache): hits
                                # seed resident pages and prefill only
                                # the suffix
    prefix_hot_pages: int = 512  # device-resident page budget; pages a
                                 # live slot references are pinned past it
    kv_tier_mb: float = 0.0    # host cold-tier budget for demoted pages,
                               # quantize+bit-pack compressed (0: demoted
                               # pages drop instead — bit-exact, no reuse
                               # after demotion)
    kv_tier_bits: int = 8      # cold-tier codebook bits per element
    preempt: bool = False      # allow the streaming frontend to suspend
                               # pooled rows mid-decode (suspend/resume
                               # preserves partial tokens; resumed greedy
                               # output is bit-identical to uninterrupted)


def supports_continuous_batching(cfg: ArchConfig) -> bool:
    """Bucketed prefill + slot-pool decode needs a pure-attention decoder:
    recurrent layers would integrate pad tokens into their state, absolute
    sinusoidal positions are scalar-offset only, and SWA ring compaction
    could drop real tokens behind the pads.  MoE is served dropless, with
    pads routing nothing, so no pad can displace a real token."""
    return (cfg.hybrid is None and cfg.xlstm is None and cfg.encdec is None
            and cfg.vlm is None and cfg.rope_theta > 0
            and cfg.sliding_window == 0)


def sample_tokens(logits, temps, key):
    """Per-request sampling, in-graph: rows with temp <= 0 take argmax
    (bit-identical to a pure-greedy program), others draw categorically at
    their own temperature."""
    greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[..., None]
    drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy_t, drawn)


class _CacheDonated:
    """The inject program, called with the whole pool as
    ``(pool, slots, rows, logits0, ...)``, hands the pool's cache over as
    a donated argument: the page scatter then writes the pool in place
    instead of copying every slot's cache.  Only the cache is donated:
    the previous chunk's done flags and token buffer, which overlap mode
    reads after this round's injects, stay valid."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, pool, *args):
        rest = {k: v for k, v in pool.items() if k != "cache"}
        return self.fn(rest, pool["cache"], *args)

    def _cache_size(self) -> int:      # telemetry's compile counter
        return self.fn._cache_size()


def _row(cache: dict, i: int) -> dict:
    """Batch row i of every leaf of a (n_sb, n_attn, B, S, ...) cache."""
    return {n: c[:, :, i] for n, c in cache.items()}


class SlotError(RuntimeError):
    """Slot-pool misuse: acquiring an occupied slot, releasing a free
    slot (double release), or releasing a slot on behalf of a request
    that does not own it.  Preemption makes these real hazards — a
    suspend races admission for the slot it frees — so the pool fails
    loudly instead of silently corrupting occupancy."""


class SlotPool:
    """Host-side bookkeeping for a fixed set of batch slots.

    ``rids[i]`` is the request occupying slot i (None = free).  The
    continuous scheduler (decode slots) and the offload gateway
    (remote-NN feature slots) share this discipline: work is admitted
    into free slots, one fixed-shape device program runs over the whole
    pool, and slots are released as requests finish — the compiled batch
    shape never changes."""

    def __init__(self, n_slots: int):
        self.rids: list = [None] * n_slots

    def __len__(self) -> int:
        return len(self.rids)

    def free(self) -> list[int]:
        return [i for i, r in enumerate(self.rids) if r is None]

    def acquire(self, slot: int, rid) -> None:
        if self.rids[slot] is not None:
            raise SlotError(f"slot {slot} already occupied "
                            f"by {self.rids[slot]!r}")
        self.rids[slot] = rid

    def release(self, slot: int, rid=None):
        """Free a slot and return its occupant.  A free slot raises
        (double release); passing ``rid`` asserts the expected occupant,
        so a preempting caller can never free a slot that was already
        re-admitted under a fresher request."""
        cur = self.rids[slot]
        if cur is None:
            raise SlotError(f"slot {slot} released twice (already free)")
        if rid is not None and cur != rid:
            raise SlotError(f"slot {slot} is owned by {cur!r}, "
                            f"not {rid!r}")
        self.rids[slot] = None
        return cur

    def occupied(self) -> list[tuple[int, object]]:
        return [(i, r) for i, r in enumerate(self.rids) if r is not None]

    def any_occupied(self) -> bool:
        return any(r is not None for r in self.rids)


@dataclasses.dataclass
class Suspended:
    """A request evicted mid-decode with its progress preserved.

    `request` is the request as originally submitted (prompt and full
    token budget); `generated` holds every token decoded before the
    suspension.  `submit_suspended` re-admits it through the ordinary
    prefill path — prompt + generated prefill as one longer prompt and
    the remaining budget decodes from there, so greedy output is
    bit-identical to an uninterrupted run.  `parked` (when the prefix
    cache is on) is the handle keeping the slot's pinned pages resident
    while the request waits to resume."""
    request: object
    generated: np.ndarray                  # (g,) int32 tokens so far
    deadline_at: Optional[float] = None    # absolute clock() deadline
    parked: Optional[object] = None        # PrefixCache.park handle


class ContinuousScheduler:
    """Drives a decode slot pool over an unbounded request queue.

    submit() enqueues and returns a request id; run() drains the queue and
    returns {rid: Completion}; step() advances one admit+decode segment
    (benchmarks interleave Poisson arrivals between steps).
    """

    def __init__(self, cfg: ArchConfig, params, *,
                 sched: Optional[SchedulerConfig] = None,
                 max_len: int = 256, seed: int = 0, mesh=None,
                 clock=None, faults=None, telemetry=None):
        """clock: wall-time source for request deadlines (default
        `time.monotonic`; tests inject a fake for determinism).
        faults: a `repro.serve.faults.FaultInjector` whose
        `chunk_stalled(round)` stalls decode rounds — requests then leave
        through deadline eviction instead of hanging the drain loop.
        telemetry: a `repro.serve.telemetry.Telemetry`; the module
        default is disabled, and every hook below guards on
        `tel.enabled`, so an uninstrumented run does zero extra clock
        reads, profiler annotations or device->host copies (telemetry
        stamps with its own clock and never reads `clock` — injected
        test clocks advance on every read)."""
        assert supports_continuous_batching(cfg), \
            f"{cfg.name}: continuous batching needs a pure-attention " \
            "RoPE decoder (use ServeEngine's equal-length grouping)"
        self.cfg = cfg
        self.params = params
        self.sched = sched or SchedulerConfig()
        self.max_len = max_len
        self.mesh = mesh
        self.faults = faults
        self.tel = telemetry if telemetry is not None else _telemetry.default()
        self._clock = clock if clock is not None else time.monotonic
        self._deadlines: dict[int, float] = {}   # rid -> absolute clock()
        self._round = 0
        self._key = jax.random.PRNGKey(seed)
        S = self.sched.max_slots
        L = max_len
        # the pool's KV width is a power-of-two page count so the decode
        # kernel pages it at 128 lanes and visits only the live pages (a
        # raw max_len like 152 would round to 160, which pages only as
        # one whole-width page: every row would attend the full width);
        # <2x memory, and requests still budget against max_len
        page = self.sched.page_size
        n_pages = 1 << max(1, (round_up(max_len, page) // page - 1)
                           .bit_length())
        self._kv_len = page * n_pages
        cache = bb.init_cache(cfg, S, self._kv_len)
        assert set(cache) <= set(bb._TOKEN_LEAVES), sorted(cache)
        self._pool = {
            "buf": jnp.zeros((S, L), jnp.int32),
            "gen": jnp.zeros((S,), jnp.int32),
            "done": jnp.ones((S,), bool),
            "tok": jnp.zeros((S, 1), jnp.int32),
            "cache": cache,
            "cache_len": jnp.zeros((S,), jnp.int32),
            "eos": jnp.full((S,), -1, jnp.int32),
            "max_new": jnp.ones((S,), jnp.int32),
            "temps": jnp.zeros((S,), jnp.float32),
        }
        if cfg.moe is not None:
            # the last decode chunk's expert work: held experts hit and
            # routings landed on them, summed over layers and steps
            self._pool["expert_counts"] = jnp.zeros((2,), jnp.int32)
        if mesh is not None:
            from repro.launch.mesh import axis_size, data_axes
            from repro.launch.partition import param_shardings, pool_shardings
            dsize = axis_size(mesh, data_axes(mesh))
            assert S % dsize == 0, \
                f"max_slots {S} must divide the {dsize}-way data axes so " \
                "every data shard owns a fixed strip of slots"
            # serving params are tensor-parallel only (weights resident on
            # the model axis, no FSDP gathers in the token loop); the pool
            # shards its slot axis over the data axes, so each device
            # decodes its own strip of slots with the same compiled program
            self.params = params = jax.device_put(
                params, param_shardings(params, mesh))
            self._pool = jax.device_put(
                self._pool, pool_shardings(self._pool, mesh))
        self._slots = SlotPool(S)
        self._queue: deque = deque()           # (rid, Request)
        self._staging: list[dict] = []         # chunked-prefill admissions
        self._results: dict[int, object] = {}
        self._next_rid = 0
        # suspend/resume bookkeeping: the request as submitted (so a
        # suspension can reconstruct the original prompt/budget), the
        # already-generated prefix a resumed rid must prepend to every
        # stream/Completion, and the parked prefix-pin handle to drop
        # once the resumed rid is re-pinned at admission
        self._req_of: dict[int, object] = {}
        self._resume: dict[int, np.ndarray] = {}
        self._parked_tok: dict[int, object] = {}
        self._pending: Optional[dict] = None   # in-flight chunk snapshot
        self._chunk_span = None                # args of the last chunk's span
        # streaming hook (serve.frontend): called between rounds with
        # (rid, tokens_so_far) for every live pooled request — overlap
        # rounds publish from the drained chunk's snapshot, serialized
        # rounds from the pool, so tokens stream as each chunk lands.
        # None (the default) skips the per-round buf/gen host copies
        # entirely: a non-streaming run does no extra device->host work
        self.stream_cb: Optional[object] = None
        # time-to-first-token hook (serve.frontend attaches a dict):
        # rid -> [submitted, slot acquired, inject dispatched] on the
        # telemetry clock, recorded only while telemetry is enabled; the
        # consumer pops each entry.  None (the default) records nothing
        self.ttft_marks: Optional[dict] = None
        self.prefix = None
        if self.sched.prefix_cache:
            from repro.serve.prefix_cache import PrefixCache
            self.prefix = PrefixCache(
                page, hot_pages=self.sched.prefix_hot_pages,
                cold_bytes=int(self.sched.kv_tier_mb * (1 << 20)),
                bits=self.sched.kv_tier_bits)

        def _prefill(params, tokens, lengths, *, max_len):
            return bb.prefill(cfg, params, {"tokens": tokens},
                              max_len=max_len, lengths=lengths)

        self._prefill = jax.jit(_prefill,      # compiles once per bucket
                                static_argnames=("max_len",))
        self._prefill_chunk = jax.jit(         # compiles once per bucket
            partial(bb.prefill_chunk, cfg),
            static_argnames=("attend_width",))
        self._inject = _CacheDonated(jax.jit(self._inject_impl,
                                             donate_argnums=(1,)))
        # the chunk updates the pool in place on every backend: no pool
        # leaf read before a dispatch may be read after it
        self._chunk = jax.jit(self._chunk_impl, donate_argnums=(1,))

    # ------------------------------------------------------------- device --

    def _inject_impl(self, pool, cache, slots, rows, logits0, prompt_lens,
                     eos, max_new, temps, key):
        """Seed freshly prefilled requests into pool slots, in-graph.
        pool: the pool without its cache; cache: the pool's cache,
        donated and updated in place.

        slots: (G,) target slot per group row; dummy rows (group padding)
        carry slot == max_slots and are dropped by the scatters.  The
        first token of each request is sampled here from the prefill
        logits, mirroring the equal-length engine loop.

        rows arrive at the bucket's page-rounded width, so the cache
        scatter copies only the pages the prompt covers; whatever the
        slot's previous occupant left past that width stays in place and
        is masked out of attention until a decode write overtakes it.
        """
        S, L = pool["buf"].shape
        tok0 = sample_tokens(logits0, temps, key)
        row0 = jnp.zeros((slots.shape[0], L), jnp.int32).at[:, 0].set(tok0)
        new = dict(pool)
        new["buf"] = pool["buf"].at[slots].set(row0, mode="drop")
        new["gen"] = pool["gen"].at[slots].set(1, mode="drop")
        new["done"] = pool["done"].at[slots].set(
            (tok0 == eos) | (max_new <= 1), mode="drop")
        new["tok"] = pool["tok"].at[slots].set(tok0[:, None], mode="drop")

        def put_pages(leaf, r):
            W = min(leaf.shape[3], r.shape[3])   # KV-axis capacities
            return leaf.at[:, :, slots, :W].set(
                r[:, :, :, :W].astype(leaf.dtype), mode="drop")

        new["cache"] = jax.tree.map(put_pages, cache, rows)
        new["cache_len"] = pool["cache_len"].at[slots].set(
            prompt_lens, mode="drop")
        new["eos"] = pool["eos"].at[slots].set(eos, mode="drop")
        new["max_new"] = pool["max_new"].at[slots].set(max_new, mode="drop")
        new["temps"] = pool["temps"].at[slots].set(temps, mode="drop")
        return new

    def _chunk_impl(self, params, pool, active, key, n_steps):
        """Up to n_steps decode steps over the whole pool as one
        while_loop; exits early when every occupied slot is done.
        n_steps is traced, so segment length never recompiles."""
        S, L = pool["buf"].shape

        def cond(state):
            step, pool, _ = state
            return (step < n_steps) & jnp.any(active & ~pool["done"])

        def body(state):
            step, pool, key = state
            run = active & ~pool["done"]
            logits, cache, counts = bb.decode_step_counted(
                self.cfg, params, pool["tok"], pool["cache"],
                pool["cache_len"], run)
            key, sub = jax.random.split(key)
            t = sample_tokens(logits, pool["temps"], sub)
            pos = jnp.where(run, pool["gen"], L)     # OOB rows -> dropped
            buf = pool["buf"].at[jnp.arange(S), pos].set(t, mode="drop")
            gen = pool["gen"] + run.astype(jnp.int32)
            done = pool["done"] | (run & ((t == pool["eos"])
                                          | (gen >= pool["max_new"])))
            # only running rows advance their depth: done/free slots keep
            # cache_len frozen (and evict resets it)
            new = dict(pool, buf=buf, gen=gen, done=done, cache=cache,
                       tok=jnp.where(run[:, None], t[:, None], pool["tok"]),
                       cache_len=pool["cache_len"] + run.astype(jnp.int32))
            if "expert_counts" in pool:
                new["expert_counts"] = pool["expert_counts"] + counts
            return step + 1, new, key

        if "expert_counts" in pool:
            pool = dict(pool, expert_counts=jnp.zeros_like(
                pool["expert_counts"]))
        _, pool, key = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), pool, key))
        return pool, key

    # --------------------------------------------------------------- host --

    def _span(self, name: str):
        """Wall span on the scheduler track (profiler name
        ``scheduler/<name>``); shared no-op when telemetry is disabled
        (no clock read, no annotation, no allocation)."""
        if not self.tel.enabled:
            return _NULL
        return self.tel.span(name, track="scheduler", cat="sched",
                             round=self._round)

    def _stamp(self, rids, i: int) -> None:
        """Time-to-first-token mark ``i`` (1: slot acquired, 2: inject
        dispatched) for each rid, one telemetry clock read for all."""
        marks = self.ttft_marks
        if marks is None or not self.tel.enabled:
            return
        t = self.tel.clock()
        for rid in rids:
            m = marks.get(rid)
            if m is not None:
                m[i] = t

    def _export_round_gauges(self) -> None:
        """The gauges sampled after every round while telemetry is
        enabled: pool occupancy, backlog, staging admissions."""
        m = self.tel.metrics
        m.gauge("sched.pool_occupancy").set(
            sum(r is not None for r in self._slots.rids))
        m.gauge("sched.backlog").set(self.backlog())
        m.gauge("sched.staging").set(len(self._staging))

    def export_metrics(self) -> None:
        """Refresh the round gauges, the decode-chunk and inject compile
        counters, and the re-export of the prefix cache's `stats` dict
        into the registry.  Called at the end of `run()` and by the
        launcher before its final dump (rounds refresh only the gauges)."""
        tel = self.tel
        if not tel.enabled:
            return
        m = tel.metrics
        self._export_round_gauges()
        tel.note_compiles("sched.decode_chunk", self._chunk,
                          shape=f"slots{len(self._slots)}")
        tel.note_compiles("sched.inject", self._inject,
                          shape=f"slots{len(self._slots)}")
        if self.prefix is not None:
            for k, v in self.prefix.stats.items():
                m.gauge(f"prefix.{k}").set(v)
            m.gauge("prefix.hit_rate").set(self.prefix.hit_rate)
            m.gauge("prefix.hot_pages").set(self.prefix.n_hot)
            m.gauge("prefix.cold_pages").set(self.prefix.n_cold)
            m.gauge("prefix.cold_used_bytes").set(
                self.prefix.cold_used_bytes)

    def _bucket_of(self, prompt_len: int) -> int:
        fits = [b for b in self.sched.buckets
                if prompt_len <= b <= self.max_len]
        if fits:
            return min(fits)
        # a prompt above every configured bucket still buckets at page
        # granularity: returning the raw length would compile a fresh
        # prefill per distinct long-prompt length
        return min(round_up(prompt_len, self.sched.page_size), self.max_len)

    def submit(self, request, *, deadline_at=None) -> int:
        """deadline_at: absolute deadline on this scheduler's clock()
        timeline, overriding request.deadline_s — used by the streaming
        frontend, which fixes deadlines at admission time rather than at
        the (later) instant the feeder releases the request."""
        T = len(request.tokens)
        assert T >= 1, "empty prompt"
        assert request.max_new_tokens >= 1, "max_new_tokens must be >= 1"
        bucket = self._bucket_of(T)
        assert max(bucket, T + request.max_new_tokens) <= self.max_len, \
            f"prompt {T} (+{request.max_new_tokens} new, bucket {bucket}) " \
            f"exceeds scheduler max_len {self.max_len}"
        assert request.extras is None, \
            "the continuous scheduler serves token-only requests"
        rid = self._next_rid
        self._next_rid += 1
        if deadline_at is not None:
            self._deadlines[rid] = float(deadline_at)
        elif getattr(request, "deadline_s", None) is not None:
            assert request.deadline_s > 0, "deadline_s must be > 0"
            self._deadlines[rid] = self._clock() + request.deadline_s
        self._req_of[rid] = request
        self._queue.append((rid, request))
        if self.ttft_marks is not None and self.tel.enabled:
            self.ttft_marks[rid] = [self.tel.clock(), None, None]
        return rid

    @property
    def _slot_rid(self) -> list:
        """Slot occupancy (kept as the historical attribute name: the
        steady-state benchmark polls it between steps)."""
        return self._slots.rids

    def backlog(self) -> int:
        """Requests admitted but not yet pooled (queued + staging) — the
        depth a frontend's feeder meters against."""
        return len(self._queue) + len(self._staging)

    def has_work(self) -> bool:
        """True while anything is queued, staging, or pooled."""
        return bool(self._queue or self._staging
                    or self._slots.any_occupied())

    def pop_completion(self, rid: int):
        """Remove and return one finished request's Completion.  The
        streaming frontend collects completions round by round from
        `step()`'s return value; `run()` keeps its collect-everything
        semantics for batch callers."""
        return self._results.pop(rid)

    # ------------------------------------------------ suspend / resume --

    def suspend(self, rid: int) -> Optional[Suspended]:
        """Evict a pooled request mid-decode, preserving its progress.

        Returns None when the row has in fact already finished (its
        Completion drains normally next round — the caller should pick
        another victim).  Reading the pool blocks on the in-flight chunk
        in overlap mode, so the suspension captures every token decoded
        so far; the pending snapshot's same-occupant eligibility guard
        then skips the released slot, exactly as it does for any slot
        freed and re-admitted between a dispatch and its drain.  Pinned
        prefix pages are parked (refs held) so a prompt resume can still
        seed them; the pages are released when the resumed admission
        re-pins, or when the suspension is discarded."""
        slot = next((i for i, r in enumerate(self._slot_rid) if r == rid),
                    None)
        assert slot is not None and slot not in self._staging_slots(), \
            f"rid {rid} is not pooled (queued/staging rows cannot suspend)"
        buf = np.asarray(self._pool["buf"])
        gen = np.asarray(self._pool["gen"])
        if np.asarray(self._pool["done"])[slot]:
            return None
        toks = buf[slot, :gen[slot]].astype(np.int32)
        prefix = self._resume.pop(rid, None)
        if prefix is not None:
            toks = np.concatenate([prefix, toks])
        n_pre = 0 if prefix is None else len(prefix)
        sub = self._req_of.pop(rid)
        # undo a previous resume's prompt extension: the Suspended record
        # always carries the *original* request plus all tokens so far
        orig = dataclasses.replace(
            sub,
            tokens=np.asarray(sub.tokens, np.int32)[:len(sub.tokens) - n_pre],
            max_new_tokens=sub.max_new_tokens + n_pre, deadline_s=None)
        parked = None
        if self.prefix is not None:
            parked = self.prefix.park(slot, ("suspend", rid))
        self._unpark(rid)                      # resumed-but-never-admitted
        self._slots.release(slot, rid)
        self._pool["cache_len"] = self._pool["cache_len"].at[slot].set(0)
        deadline_at = self._deadlines.pop(rid, None)
        if self.tel.enabled:
            self.tel.counter("sched.evicted", reason="preempted").inc()
        return Suspended(orig, toks, deadline_at, parked)

    def submit_suspended(self, sus: Suspended, *, deadline_at=None) -> int:
        """Re-admit a suspended request through the ordinary prefill
        path: prompt + generated-so-far tokens prefill as one longer
        prompt (chunked prefill and prefix-page seeding apply as for any
        admission), the next token samples from the resumed prefill's
        logits, and streams/Completion carry the full token sequence.
        Greedy rows continue as an uninterrupted run would: argmax
        sampling is RNG-free, and the resumed prefill agrees with the
        uninterrupted run's within float32 reassociation, however the
        prompt is segmented.  Returns the new rid."""
        req = sus.request
        gen = np.asarray(sus.generated, np.int32)
        remaining = req.max_new_tokens - len(gen)
        assert remaining >= 1, \
            "suspended request has exhausted its token budget"
        cont = dataclasses.replace(
            req, tokens=np.concatenate([np.asarray(req.tokens, np.int32),
                                        gen]),
            max_new_tokens=remaining, deadline_s=None)
        if deadline_at is None:
            deadline_at = sus.deadline_at
        rid = self.submit(cont, deadline_at=deadline_at)
        if len(gen):
            self._resume[rid] = gen
        if sus.parked is not None:
            self._parked_tok[rid] = sus.parked
        if self.tel.enabled:
            self.tel.counter("sched.resumed").inc()
        return rid

    def discard_suspended(self, sus: Suspended) -> None:
        """Drop a suspension that will never resume (its frontend shed
        it): release the parked prefix pins.  The generated tokens live
        in the Suspended record — the caller resolves the request with
        them, so nothing is silently lost."""
        if self.prefix is not None and sus.parked is not None:
            self.prefix.unpark(sus.parked)

    def _unpark(self, rid: int) -> None:
        """Drop the parked pins a resumed rid carried, once its new
        admission has pinned (or once it resolves without admitting)."""
        tok = self._parked_tok.pop(rid, None)
        if tok is not None and self.prefix is not None:
            self.prefix.unpark(tok)

    def _resume_prefix(self, rid) -> Optional[np.ndarray]:
        return self._resume.get(rid)

    def _free_slots(self) -> list[int]:
        return self._slots.free()

    def _staging_slots(self) -> set:
        return {st["slot"] for st in self._staging}

    def _copy_width(self, bucket: int) -> int:
        """Token width of the cache rows an admission copies into the
        pool: the bucket rounded up to whole pages (never the full pool
        width)."""
        return min(self._kv_len, round_up(bucket, self.sched.page_size))

    def _is_long(self, req) -> bool:
        seg = self.sched.prefill_segment
        return bool(seg) and self._bucket_of(len(req.tokens)) > seg

    def _has_hit(self, req) -> bool:
        """True when the request's leading pages are resident: it will
        admit through a prefix plan when it leads, so group formation
        skips it (a group row would re-prefill the prefix)."""
        return (self.prefix is not None
                and self.prefix.lookup(req.tokens)[1] > 0)

    def _plan_one(self):
        """Form one admission decision from the queue head: a bucket
        group (returned as a prepared dict of numpy prefill inputs, its
        slots acquired), a staging claim (returns True), or None when
        nothing can admit.  Pure host work — the device is untouched, so
        overlap mode runs this while a decode chunk is in flight.

        Groups are formed in FIFO order keyed by the head request's
        bucket, so the queue head is always in the next group — no
        request can be starved by a stream of other-bucket arrivals.  A
        long head (bucket > prefill_segment) claims a slot and stages
        instead; while a staging is already in flight the head's wait is
        bounded by its remaining segments, and the first short group
        behind it keeps the pool fed.

        With the prefix cache on, a short lead whose leading pages are
        resident leads a *prefix plan* (seed the pages, prefill only the
        suffixes) batched with queued requests sharing its bucket and
        hit depth, and ordinary groups are formed from hit-free requests
        only; a hit-carrying request that can't join just waits to lead,
        which FIFO bounds the same way it bounds buckets.
        """
        free = self._free_slots()
        if not free or not self._queue:
            return None
        head_rid, head_req = self._queue[0]
        if self._is_long(head_req):
            if not self._staging:
                self._queue.popleft()
                self._start_staging(head_rid, head_req, free[0])
                return True
            shorts = [(r, q) for r, q in self._queue
                      if not self._is_long(q)]
            if not shorts:
                return None
            lead_rid, lead_req = shorts[0]
        else:
            lead_rid, lead_req = head_rid, head_req
        if self.prefix is not None:
            n_hit = self.prefix.lookup(lead_req.tokens)[1]
            if n_hit:
                return self._plan_prefix_group(lead_req, free, n_hit)
        head_bucket = self._bucket_of(len(lead_req.tokens))

        G = self.sched.prefill_group
        take, keep = [], deque()
        for rid, req in self._queue:
            if (len(take) < min(len(free), G) and not self._is_long(req)
                    and self._bucket_of(len(req.tokens)) == head_bucket
                    and not self._has_hit(req)):
                take.append((rid, req))
            else:
                keep.append((rid, req))
        if not take:
            return None
        self._queue = keep

        tokens = np.zeros((G, head_bucket), np.int32)
        lengths = np.ones((G,), np.int32)        # dummies: 1 valid token
        slots = np.full((G,), self.sched.max_slots, np.int32)
        eos = np.full((G,), -1, np.int32)
        max_new = np.ones((G,), np.int32)
        temps = np.zeros((G,), np.float32)
        pkeys = []
        for g, ((rid, req), slot) in enumerate(zip(take, free)):
            T = len(req.tokens)
            tokens[g, :T] = np.asarray(req.tokens, np.int32)
            lengths[g] = T
            slots[g] = slot
            eos[g] = req.eos_id
            max_new[g] = req.max_new_tokens
            temps[g] = req.temperature
            self._slots.acquire(slot, rid)
            if self.prefix is not None:
                pkeys.append(self.prefix.lookup(req.tokens)[0])
        self._stamp([rid for rid, _ in take], 1)
        return {"bucket": head_bucket, "tokens": tokens, "lengths": lengths,
                "slots": slots, "eos": eos, "max_new": max_new,
                "temps": temps, "pkeys": pkeys,
                "rids": [rid for rid, _ in take]}

    def _plan_prefix_group(self, lead_req, free: list[int],
                           n_hit: int) -> Optional[dict]:
        """Form one batched prefix-hit admission: up to prefill_group
        short requests sharing the lead's bucket AND resident-page depth
        (their seeded widths — and so the suffix-chunk program — match;
        the pages themselves may differ per row).  Batching keeps a hit
        wave as cheap per request as a group prefill: one chunked suffix
        pass and one inject serve the whole wave."""
        bucket = self._bucket_of(len(lead_req.tokens))
        G = self.sched.prefill_group
        take, keep = [], deque()
        for rid, req in self._queue:
            if (len(take) < min(len(free), G) and not self._is_long(req)
                    and self._bucket_of(len(req.tokens)) == bucket):
                keys, h = self.prefix.lookup(req.tokens)
                if h == n_hit:
                    take.append((rid, req, keys))
                    continue
            keep.append((rid, req))
        assert take, "the hit lead must join its own prefix group"
        self._queue = keep
        for (rid, _, _), slot in zip(take, free):
            self._slots.acquire(slot, rid)
        self._stamp([rid for rid, _, _ in take], 1)
        return {"prefix": True, "take": take, "slots": free[:len(take)],
                "bucket": bucket, "n_hit": n_hit}

    def _admit(self) -> None:
        """Plan and launch every admission the queue and free slots
        allow.  Each plan launches as it forms: a group's pin() lands
        its pages in the prefix index (host-side) before the next plan's
        lookup runs, so same-round arrivals sharing a prefix hit on the
        very first wave instead of waiting for the next round."""
        while True:
            g = self._plan_one()
            if g is None:
                return
            if g is not True:
                self._launch(g)

    def _launch(self, g: dict) -> None:
        """Dispatch one prepared admission plan."""
        if g.get("prefix"):
            self._launch_prefix(g)
        else:
            self._launch_group(g)

    def _launch_group(self, g: dict) -> None:
        """Dispatch one prepared group: per-bucket prefill + in-graph
        inject.  Async — the host returns as soon as the work is queued.
        With the prefix cache on, every row's shareable pages are
        registered (sliced from the prefill rows) and pinned for the
        slot's lifetime."""
        logits0, rows, _ = self._prefill(
            self.params, jnp.asarray(g["tokens"]), jnp.asarray(g["lengths"]),
            max_len=self._copy_width(g["bucket"]))
        if self.tel.enabled:
            self.tel.note_compiles("sched.prefill", self._prefill,
                                   shape=f"bucket{g['bucket']}")
            self.tel.counter("sched.admitted", path="group").inc(
                int((g["slots"] < self.sched.max_slots).sum()))
        if self.prefix is not None:
            for i, (keys, slot) in enumerate(zip(g["pkeys"], g["slots"])):
                self.prefix.record(len(keys), 0)
                if keys:
                    self.prefix.pin(int(slot), keys, _row(rows, i))
        for rid in g["rids"]:          # after pin: parked pages stay hot
            self._unpark(rid)          # until the new pins hold them
        self._key, sub = jax.random.split(self._key)
        self._pool = self._inject(
            self._pool, jnp.asarray(g["slots"]), rows, logits0,
            jnp.asarray(g["lengths"]), jnp.asarray(g["eos"]),
            jnp.asarray(g["max_new"]), jnp.asarray(g["temps"]), sub)
        self._stamp(g["rids"], 2)

    def _launch_prefix(self, g: dict) -> None:
        """Admit a wave of prefix-hit requests in one batch: seed each
        row's resident pages into a fresh G-row cache (the copy-on-write
        copies, hoisted to admission — the pool's dense layout makes
        inject the slot's first and only write below the prompt), then
        prefill just the suffixes in page-width chunks attending at the
        full bucket width (the same segment-vs-one-shot bit-identity
        `_advance_staging` relies on).  `prefill_chunk` gathers logits
        per row, so rows whose prompts end in different chunks each keep
        the logits of the chunk holding their final token; a short row's
        later chunks only write pad keys above its prompt, exactly what
        a full-width group prefill leaves there.  One inject lands the
        whole wave through the ordinary page-granular scatter — the same
        compiled program the group path uses.  Every device op is
        dispatched async, so overlap mode pipelines a prefix wave behind
        the in-flight decode chunk like any other."""
        take, H = g["take"], g["n_hit"]
        page = self.sched.page_size
        G = self.sched.prefill_group
        Wc = self._copy_width(g["bucket"])
        seeded = H * page
        kvs = [self.prefix.fetch(keys[:H]) for _, _, keys in take]
        pad = G - len(take)
        cache = dict(bb.init_cache(self.cfg, G, Wc))
        for n, c in cache.items():
            seed = jnp.stack([kv[n] for kv in kvs]
                             + [jnp.zeros_like(kvs[0][n])] * pad, axis=2)
            cache[n] = c.at[:, :, :, :seeded].set(seed.astype(c.dtype))
        toks = np.zeros((G, Wc), np.int32)
        lengths = np.ones((G,), np.int32)        # dummies: 1 valid token
        slots = np.full((G,), self.sched.max_slots, np.int32)
        eos = np.full((G,), -1, np.int32)
        max_new = np.ones((G,), np.int32)
        temps = np.zeros((G,), np.float32)
        for i, ((rid, req, _), slot) in enumerate(zip(take, g["slots"])):
            T = len(req.tokens)
            toks[i, :T] = np.asarray(req.tokens, np.int32)
            lengths[i] = T
            slots[i] = slot
            eos[i] = req.eos_id
            max_new[i] = req.max_new_tokens
            temps[i] = req.temperature
        logits0 = None
        for d in range(seeded, Wc, page):     # seeded <= T-1 on hit rows
            last = np.clip(lengths - 1 - d, 0, page - 1).astype(np.int32)
            lg, cache = self._prefill_chunk(
                self.params, jnp.asarray(toks[:, d:d + page]), cache,
                jnp.int32(d), attend_width=g["bucket"],
                last_index=jnp.asarray(last), lengths=jnp.asarray(lengths))
            ends_here = (d <= lengths - 1) & (lengths - 1 < d + page)
            logits0 = lg if logits0 is None else jnp.where(
                jnp.asarray(ends_here)[:, None], lg, logits0)
            if d + page >= int(lengths.max()):
                break
        if self.tel.enabled:
            self.tel.note_compiles("sched.prefill_chunk", self._prefill_chunk,
                                   shape=f"bucket{g['bucket']}")
            self.tel.counter("sched.admitted", path="prefix").inc(len(take))
        for i, ((rid, _, keys), slot) in enumerate(zip(take, g["slots"])):
            self.prefix.record(len(keys), H)
            self.prefix.pin(int(slot), keys, _row(cache, i))
            self._unpark(rid)          # resumed rows: new pins now hold
        self._key, sub = jax.random.split(self._key)
        self._pool = self._inject(
            self._pool, jnp.asarray(slots), cache, logits0,
            jnp.asarray(lengths), jnp.asarray(eos),
            jnp.asarray(max_new), jnp.asarray(temps), sub)
        self._stamp([rid for rid, _, _ in take], 2)

    # ------------------------------------------------- chunked prefill --

    def _start_staging(self, rid: int, req, slot: int) -> None:
        """Claim a slot for a long admission; its prompt prefills one
        `prefill_segment`-token slice per scheduling round.  Resident
        prefix pages seed the staged cache in whole segments (staging
        advances a segment at a time, so a partial segment can't be
        skipped) and `depth` starts past them — a long re-admission of a
        shared header pays only its tail's segments."""
        seg = self.sched.prefill_segment
        page = self.sched.page_size
        bucket = self._bucket_of(len(req.tokens))
        T = len(req.tokens)
        n_segs = round_up(bucket, seg) // seg
        toks = np.zeros((n_segs * seg,), np.int32)
        toks[:T] = np.asarray(req.tokens, np.int32)
        cache = bb.init_cache(self.cfg, 1, n_segs * seg)
        depth, keys = 0, []
        if self.prefix is not None:
            keys, n_hit = self.prefix.lookup(req.tokens)
            depth = (n_hit * page // seg) * seg    # whole segments only
            self.prefix.record(len(keys), depth // page)
            if depth:
                kv = self.prefix.fetch(keys[:-(-depth // page)])
                cache = {n: c.at[:, :, :, :depth].set(
                    kv[n][:, :, None, :depth].astype(c.dtype))
                    for n, c in cache.items()}
        self._slots.acquire(slot, rid)
        self._stamp([rid], 1)
        self._staging.append({
            "rid": rid, "req": req, "slot": slot, "depth": depth, "T": T,
            "bucket": bucket, "tokens": toks, "logits0": None, "keys": keys,
            # staging cache width: whole segments covering the bucket, so
            # every segment's K/V write lands without clamping
            "cache": cache,
        })

    def _advance_staging(self) -> None:
        """Run one prefill segment for the staged admission (if any).
        Attention spans the bucket width at every segment (keys past the
        segment are causally masked), one compiled program per bucket;
        the staged rows agree with a one-shot bucketed prefill within
        float32 reassociation.  Segments stop once the prompt tail has
        landed."""
        if not self._staging:
            return
        st = self._staging[0]
        seg = self.sched.prefill_segment
        d = st["depth"]
        toks = jnp.asarray(st["tokens"][None, d:d + seg])
        last = min(max(st["T"] - 1 - d, 0), seg - 1)
        logits, st["cache"] = self._prefill_chunk(
            self.params, toks, st["cache"], jnp.int32(d),
            attend_width=st["bucket"], last_index=jnp.int32(last),
            lengths=jnp.asarray([st["T"]], jnp.int32))
        if self.tel.enabled:
            self.tel.note_compiles("sched.prefill_chunk", self._prefill_chunk,
                                   shape=f"bucket{st['bucket']}")
        if d <= st["T"] - 1 < d + seg:
            st["logits0"] = logits          # segment holding the last token
        st["depth"] = d + seg
        if st["depth"] >= st["T"]:
            self._staging.remove(st)
            self._finish_staging(st)

    def _finish_staging(self, st: dict) -> None:
        """The staged cache joins the pool through the same page-granular
        inject as one-shot admissions (first token sampled in-graph)."""
        req = st["req"]
        if self.tel.enabled:
            self.tel.counter("sched.admitted", path="staged").inc()
        if self.prefix is not None and st["keys"]:
            self.prefix.pin(st["slot"], st["keys"], _row(st["cache"], 0))
        self._unpark(st["rid"])        # resumed rows: new pins now hold
        self._key, sub = jax.random.split(self._key)
        self._pool = self._inject(
            self._pool, jnp.asarray([st["slot"]]), st["cache"],
            st["logits0"], jnp.asarray([st["T"]], jnp.int32),
            jnp.asarray([req.eos_id], jnp.int32),
            jnp.asarray([req.max_new_tokens], jnp.int32),
            jnp.asarray([req.temperature], jnp.float32), sub)
        self._stamp([st["rid"]], 2)

    # ----------------------------------------------------------- loop --

    def _active_mask(self) -> np.ndarray:
        stag = self._staging_slots()
        return np.asarray([r is not None and i not in stag
                           for i, r in enumerate(self._slot_rid)])

    def _complete(self, fin: list[int], buf, gen, *,
                  timed_out: bool = False) -> list[int]:
        """Release finished slots and record their Completions; freed
        slots drop to depth 0."""
        from repro.serve.engine import Completion
        if self.tel.enabled and fin:
            self.tel.counter(
                "sched.evicted",
                reason="deadline" if timed_out else "finished").inc(len(fin))
        out = []
        for i in fin:
            rid = self._slots.release(i)
            if self.prefix is not None:
                self.prefix.release(i)     # unpin the slot's prefix pages
            self._deadlines.pop(rid, None)
            self._req_of.pop(rid, None)
            self._unpark(rid)
            toks = buf[i, :gen[i]].astype(np.int32)
            prefix = self._resume.pop(rid, None)
            if prefix is not None:         # resumed rows report the full
                toks = np.concatenate([prefix, toks])      # token stream
            self._results[rid] = Completion(toks, len(toks),
                                            timed_out=timed_out)
            out.append(rid)
        self._pool["cache_len"] = (
            self._pool["cache_len"].at[jnp.asarray(fin)].set(0))
        return out

    # ------------------------------------------------------ deadlines --

    def _expire_deadlines(self) -> list[int]:
        """Deadline-evict, between chunks, every request whose deadline
        has lapsed: queued requests resolve empty, a staging admission
        aborts its prefill and frees its slot, pooled slots evict with
        the tokens generated so far.  A request past its deadline never
        occupies device work again — under a stalled pool this is the
        exit that keeps `run()` from hanging."""
        if not self._deadlines:
            return []
        from repro.serve.engine import Completion
        now = self._clock()
        expired = {rid for rid, at in self._deadlines.items() if at <= now}
        if not expired:
            return []
        out = []
        # queued, never admitted: nothing was generated in time
        keep = deque()
        for rid, req in self._queue:
            if rid in expired:
                # a resumed request expiring in queue still resolves with
                # the tokens it generated before suspension — preemption
                # never silently drops work
                pre = self._resume.pop(rid, None)
                toks = pre if pre is not None else np.zeros((0,), np.int32)
                self._results[rid] = Completion(toks, len(toks),
                                                timed_out=True)
                self._deadlines.pop(rid)
                self._req_of.pop(rid, None)
                self._unpark(rid)
                out.append(rid)
            else:
                keep.append((rid, req))
        self._queue = keep
        # staging: abort the chunked prefill, free its claimed slot
        for st in [s for s in self._staging if s["rid"] in expired]:
            self._staging.remove(st)
            self._slots.release(st["slot"], st["rid"])
            self._deadlines.pop(st["rid"])
            self._req_of.pop(st["rid"], None)
            self._unpark(st["rid"])
            pre = self._resume.pop(st["rid"], None)
            toks = pre if pre is not None else np.zeros((0,), np.int32)
            self._results[st["rid"]] = Completion(toks, len(toks),
                                                  timed_out=True)
            out.append(st["rid"])
        # pooled: evict with partial tokens (host copy like _drain's)
        fin = [i for i, rid in enumerate(self._slot_rid)
               if rid in expired]
        if fin:
            out.extend(self._complete(
                fin, np.asarray(self._pool["buf"]),
                np.asarray(self._pool["gen"]), timed_out=True))
        return out

    def _drain(self) -> list[int]:
        """Evict finished slots: one host copy of buf/gen per segment."""
        with self._span("wait_done"):
            done = np.asarray(self._pool["done"])
            self._note_expert_counts(self._pool, self._chunk_span)
        stag = self._staging_slots()
        fin = [i for i, rid in enumerate(self._slot_rid)
               if rid is not None and done[i] and i not in stag]
        if self.stream_cb is not None:
            live = [i for i, rid in enumerate(self._slot_rid)
                    if rid is not None and i not in stag and not done[i]]
            with self._span("stream"):
                self._stream_rows(live, self._pool["buf"],
                                  self._pool["gen"], self._slot_rid)
        if not fin:
            return []
        return self._complete(fin, np.asarray(self._pool["buf"]),
                              np.asarray(self._pool["gen"]))

    def _stream_rows(self, rows: list[int], buf, gen, rids) -> None:
        """Publish tokens-so-far for still-running slots (the finishers'
        full buffers travel in their Completions instead).  One buf/gen
        host copy per streamed round — the price of streaming, paid only
        when a `stream_cb` is attached."""
        if not rows:
            return
        buf, gen = np.asarray(buf), np.asarray(gen)
        for i in rows:
            toks = buf[i, :gen[i]]
            pre = self._resume.get(rids[i])
            if pre is not None:            # resumed rows stream the full
                toks = np.concatenate([pre, toks])         # token stream
            self.stream_cb(rids[i], toks)

    def _snapshot_chunk(self, rids: list, active: np.ndarray) -> None:
        """Capture the just-dispatched chunk's observable state and start
        its device->host copies; the host blocks on them only next round,
        after the following round's work has been dispatched."""
        pend = {"done": self._pool["done"], "buf": self._pool["buf"],
                "gen": self._pool["gen"], "rids": rids, "active": active,
                "span": self._chunk_span}
        fetch = ["done"]
        if "expert_counts" in self._pool and self.tel.enabled:
            pend["expert_counts"] = self._pool["expert_counts"]
            fetch.append("expert_counts")
        try:
            # the round's one blocking read; buf/gen stay device-side and
            # are only pulled on rounds that actually evict
            for k in fetch:
                pend[k].copy_to_host_async()
        except AttributeError:          # non-Array leaves under tracing
            pass
        self._pending = pend

    def _note_expert_counts(self, pool: dict, span) -> None:
        """With telemetry on, put a decode chunk's expert work on its
        ``decode_chunk`` span: held experts hit (summed over layers and
        steps) and routings that landed on them."""
        if span is None or "expert_counts" not in pool:
            return
        hit, local = (int(x) for x in np.asarray(pool["expert_counts"]))
        span["experts_hit"] = hit
        span["routed_local"] = local

    def _drain_pending(self) -> list[int]:
        """Evict the finishers of the *previous* round's chunk.  Only
        slots that were active in that chunk AND still hold the same
        occupant are eligible: a slot freed and re-admitted in between
        carries a fresher request whose done flag this snapshot cannot
        know, and a then-staging slot's done flag is the previous
        occupant's leftover."""
        p, self._pending = self._pending, None
        if p is None:
            return []
        with self._span("wait_done"):      # the host waits on the device
            done = np.asarray(p["done"])
            self._note_expert_counts(p, p["span"])
        eligible = [i for i, rid in enumerate(self._slot_rid)
                    if rid is not None and p["active"][i]
                    and p["rids"][i] == rid]
        fin = [i for i in eligible if done[i]]
        if self.stream_cb is not None:
            # stream from the drained chunk's own snapshot: the rows are
            # consistent with the done flags just read, even though the
            # next chunk is already in flight on the device
            with self._span("stream"):
                self._stream_rows([i for i in eligible if not done[i]],
                                  p["buf"], p["gen"], p["rids"])
        if not fin:
            return []
        return self._complete(fin, np.asarray(p["buf"]),
                              np.asarray(p["gen"]))

    def _dispatch_chunk(self) -> Optional[np.ndarray]:
        """Dispatch one decode chunk over the occupied non-staging slots;
        returns the active mask used (None when nothing is decodable, or
        when a fault has this round's executor stalled — deadlines keep
        aging either way)."""
        if self.faults is not None and \
                self.faults.chunk_stalled(self._round - 1):
            return None
        active = self._active_mask()
        if not active.any():
            return None
        if self.tel.enabled:
            self.tel.counter("sched.decode_chunks").inc()
            self.tel.counter("sched.decode_rows_active").inc(
                int(active.sum()))
        self._key, sub = jax.random.split(self._key)
        # a meshed chunk traces under its mesh, so the decode kernel can
        # run per shard (`kernels.decode_attention.ops._per_shard`)
        with (jax.set_mesh(self.mesh) if self.mesh is not None else _NULL):
            self._pool, _ = self._chunk(self.params, self._pool,
                                        jnp.asarray(active), sub,
                                        jnp.int32(self.sched.chunk))
        return active

    def _decode_chunk(self) -> Optional[np.ndarray]:
        """`_dispatch_chunk` under the round's ``decode_chunk`` span,
        which records how many rows the chunk decodes (``rows``); the
        chunk's expert counts join it when its done flags are read."""
        self._chunk_span = None
        with self._span("decode_chunk") as sp:
            active = self._dispatch_chunk()
            if sp is not None and active is not None:
                sp["rows"] = int(active.sum())
                self._chunk_span = sp
        return active

    def step(self) -> list[int]:
        """One scheduling round.  Serialized mode: advance the staged
        prefill a segment, admit groups while slots are free, decode one
        chunk, block on the drain.  Overlap mode pipelines the same round
        against the device (see `_step_overlapped`).  Returns completed
        request ids (overlap mode reports a completion one round after
        its chunk, once its async done-copy has landed).  Expired
        deadlines evict first, so a deadline-carrying request never costs
        another prefill segment or decode chunk past its budget."""
        self._round += 1                # 0-based round index while inside:
                                        # _dispatch_chunk sees _round - 1
        if self.faults is not None and self.faults.crashed(self._round - 1):
            # scripted engine death: the pool and every in-flight chunk
            # are lost mid-round.  serve.recovery replays the journal
            # into a fresh stack and regenerates the lost tokens
            # bit-identically
            from repro.serve.faults import EngineCrashError
            raise EngineCrashError(
                f"scripted engine crash at round {self._round - 1}")
        with self._span("round"):
            expired = self._expire_deadlines()
            if self.sched.overlap:
                out = expired + self._step_overlapped()
            else:
                with self._span("prefill_segment"):
                    self._advance_staging()
                with self._span("admit"):
                    self._admit()
                dispatched = self._decode_chunk()
                if dispatched is None:
                    out = expired
                else:
                    with self._span("evict"):
                        out = expired + self._drain()
        if self.tel.enabled:
            self._export_round_gauges()
        return out

    def _step_overlapped(self) -> list[int]:
        """One pipelined round: round k's prefill work is dispatched, and
        its admissions bucketed/tokenized, while round k-1's chunk is
        still in flight — the staged segment, the injects and the decode
        chunk queue back-to-back on the device with no host sync between
        them, and the host's one blocking read (round k-1's done flags,
        whose device->host copy started at dispatch) sits behind a full
        round of queued work instead of stalling an idle device.  Evict/
        admit timing is round-identical to serialized mode: chunk k-1's
        finishers free their slots before chunk k dispatches, a second
        admission pass fills them, and completions simply report one
        round late."""
        with self._span("prefill_segment"):
            self._advance_staging()            # prefill segment (async)
        with self._span("admit"):
            self._admit()                      # overlap chunk k-1: bucket/
                                               # tokenize + inject dispatch
        with self._span("evict"):
            out = self._drain_pending()        # round k-1 lands (no idle
        with self._span("admit"):
            self._admit()                      # wait); freed slots admit
                                               # before this round's chunk
        rids = list(self._slot_rid)            # occupancy at dispatch time
        active = self._decode_chunk()
        if active is not None:
            self._snapshot_chunk(rids, active)
        return out

    def run(self) -> dict:
        """Drain queue and pool; returns (and forgets) {rid: Completion}."""
        while self._queue or self._staging or self._slots.any_occupied():
            self.step()
        self.export_metrics()
        out, self._results = self._results, {}
        return out
