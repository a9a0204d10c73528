"""Language-model serving cells: the streaming frontend over the
continuous scheduler, driven on the wall clock.

The window drives `StreamingFrontend.submit` / `.step`; underneath run the
scheduler's bucketed and chunked prefill, the prefix cache, the inject
and the paged decode chunk.  The check compares what that path served,
the first-token logits of each sampled request and its greedy tokens,
with the configuration's plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time

import jax
import numpy as np

import schedule as sched_gen

GRACE_S = 20.0          # a measured request may finish this long after
                        # the window closes (or the profiler stops, if
                        # later); after that it counts as failed


@dataclasses.dataclass
class Record:
    idx: int
    prompt: np.ndarray
    max_new: int
    measured: bool
    t_due: float = 0.0             # monotonic seconds
    t_submit: float | None = None
    t_first: float | None = None
    t_last: float | None = None
    t_done: float | None = None
    n_tokens: int = 0
    token_ts: list = dataclasses.field(default_factory=list)
    tokens: np.ndarray | None = None
    status: str = ""
    rid: int = -1


class FirstLogits:
    """Wraps the scheduler's inject program to keep, for every admission,
    the prefill logits that its first tokens are sampled from and the
    frontend request of each slot.  Only references are kept: nothing is
    read from the device until the window has closed, and the inject
    program itself runs as before (its logits are never donated)."""

    def __init__(self, fe):
        self.fe, self.sched = fe, fe.sched
        self.inner = self.sched._inject
        self.calls: list = []
        self.sched._inject = self

    def __call__(self, pool, slots, rows, logits0, *rest):
        owners = [self.fe._from_sched.get(r) for r in self.sched._slot_rid]
        self.calls.append((slots, logits0, owners))
        return self.inner(pool, slots, rows, logits0, *rest)

    def _cache_size(self) -> int:          # telemetry's compile counter
        return self.inner._cache_size()

    def by_rid(self) -> dict:
        """Frontend request id -> its first-token logits (host copy)."""
        out = {}
        for slots, logits0, owners in self.calls:
            for i, s in enumerate(np.asarray(slots)):
                if s < len(owners) and owners[s] is not None:
                    out[owners[s]] = (logits0, i)
        return {rid: np.asarray(lg[i]) for rid, (lg, i) in out.items()}


class LMServing:
    """One qwen2-family serving deployment, built from its configuration
    file, its seed and a traffic mix."""

    def __init__(self, config: dict, ref, mix: dict, seed: int,
                 seconds: float, tel):
        from repro.configs import get_config
        from repro.serve.frontend import StreamingFrontend
        from repro.serve.scheduler import SchedulerConfig
        self.config, self.ref, self.mix = config, ref, mix
        self.seed, self.seconds, self.tel = seed, seconds, tel
        self.model = config["model"]
        m = self.model
        fields = {k: m[k] for k in (
            "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "qkv_bias", "rope_theta", "tie_embeddings",
            "norm", "param_dtype")}
        self.cfg = dataclasses.replace(get_config(config["program_arch"]),
                                       **fields)
        sv = config["serving"]
        sc = dict(sv["scheduler"])
        sc["buckets"] = tuple(sc["buckets"])
        self.sched_cfg = SchedulerConfig(**sc)
        self.max_len = sv["max_len"]
        self.params = ref.make_params(m, sched_gen.seed32(seed, 0))
        self.fe = StreamingFrontend(self.cfg, self.params,
                                    sched=self.sched_cfg,
                                    max_len=self.max_len, telemetry=tel)
        self.reqs = sched_gen.lm_schedule(mix, seed, seconds, m["vocab"])
        self.records: list[Record] = []
        self.gauges: list[dict] = []   # per-round scheduler gauges (traced),
                                       # of the rounds that start in the window
        self.first = None
        self.firsts: dict = {}

    # ----------------------------------------------------------- set-up --

    def warm_up(self) -> None:
        """Compile every program this cell's requests will run: for each
        bucket the traffic reaches, a prompt as long as its longest, of
        fresh tokens, with two new tokens (prefill or each chunked-prefill
        segment, inject, decode chunk, prefix pinning); then the host-side
        slot scatters at every batch size.
        No prompt of the window is served here, so no page it could hit
        is made resident."""
        import jax.numpy as jnp
        from repro.serve.engine import Request
        s = self.fe.sched
        longest = {}
        for r in self.reqs:
            b = s._bucket_of(len(r.prompt))
            longest[b] = max(longest.get(b, 0), len(r.prompt))
        rng = sched_gen.rng_for(self.seed, 5)
        for b in sorted(longest):
            toks = rng.integers(0, self.model["vocab"], longest[b])
            self.fe.submit(Request(tokens=toks.astype(np.int32),
                                   max_new_tokens=2))
            self.fe.run()
        for n in range(1, self.sched_cfg.max_slots + 1):
            x = jnp.zeros((self.sched_cfg.max_slots,), jnp.int32)
            x.at[jnp.asarray(list(range(n)))].set(0).block_until_ready()

    # ----------------------------------------------------------- window --

    def _on_events(self, events, rec_of) -> None:
        from repro.serve.frontend import Delta, Finish, FirstToken
        for ev in events:
            rec = rec_of.get(ev.rid)
            if rec is None:
                continue
            if isinstance(ev, FirstToken):
                rec.t_first = ev.t
                rec.t_last = ev.t
                rec.token_ts.append(ev.t)
            elif isinstance(ev, Delta):
                rec.t_last = ev.t
                rec.token_ts.append(ev.t)
            elif isinstance(ev, Finish):
                rec.t_done = ev.t
                rec.status = ev.status
                rec.tokens = np.asarray(ev.tokens)
                rec.n_tokens = len(ev.tokens)

    def _submit(self, r, now, rec_of) -> Record:
        from repro.serve.engine import Request
        rec = Record(r.idx, r.prompt, r.max_new, r.measured)
        rec.t_submit = now
        rid = self.fe.submit(Request(tokens=r.prompt,
                                     max_new_tokens=r.max_new))
        rec.rid = rid
        rec_of[rid] = rec
        self.records.append(rec)
        return rec

    def run_window(self, profiler) -> None:
        """Drive the cell's traffic for `seconds`: submit each request at
        its due instant, and after the window keep the load on until
        every measured request has finished (or GRACE_S has passed)."""
        ann = jax.profiler.TraceAnnotation
        fe, tel = self.fe, self.tel
        rec_of: dict = {}
        sample_gauges = tel.enabled
        self.first = FirstLogits(fe)
        pending = self.reqs
        n_measured = sum(r.measured for r in pending)
        i = 0
        t0 = time.monotonic()
        t_end = t0 + self.seconds
        while True:
            now = time.monotonic()
            profiler.poll(now - t0)
            with ann("bench/submit"):
                while i < len(pending) and t0 + pending[i].t_due <= now:
                    rec = self._submit(pending[i], now, rec_of)
                    rec.t_due = t0 + pending[i].t_due
                    i += 1
            if now >= t_end and i >= n_measured and all(
                    r.t_done is not None for r in self.records if r.measured):
                break
            # stopping the profiler stalls the host: the grace runs after it
            if now >= max(t_end, profiler.released or 0.0) + GRACE_S:
                break
            if fe.has_work():
                with ann("bench/frontend.step"):
                    evs = fe.step()
                self._on_events(evs, rec_of)
                if sample_gauges and now < t_end:
                    self.gauges.append(_gauges(tel))
            elif i < len(pending):
                with ann("bench/await_arrival"):
                    time.sleep(max(0.0, min(
                        t0 + pending[i].t_due - time.monotonic(), 2e-3)))
            else:
                break
        profiler.finish()
        self.t0, self.t_end = t0, t_end

    # ---------------------------------------------------------- results --

    def describe(self) -> dict:
        """Diagnostics for standard error: the spread of time to first
        token, how late the generator submitted, completions."""
        import stats
        recs = [r for r in self.records if r.measured]
        tt = stats.ttft_values_ms(recs)
        late = [(r.t_submit - r.t_due) * 1e3 for r in recs
                if r.t_submit is not None]
        out = {"measured": len(recs),
               "finished": sum(r.t_done is not None for r in self.records),
               "window_tokens": self.window_tokens()}
        if tt:
            out.update({f"ttft_p{q}_ms": stats.percentile(tt, q)
                        for q in (50, 75, 90, 95)})
            out["ttft_mean_ms"] = float(np.mean(tt))
        if late:
            out["submit_late_p95_ms"] = stats.percentile(late, 95)
        return out

    def window_tokens(self) -> int:
        """Tokens streamed to clients inside the window."""
        return sum(1 for r in self.records for t in r.token_ts
                   if self.t0 <= t <= self.t_end)

    def release(self) -> None:
        """Copy the served first-token logits to the host, then free the
        program's state before the reference runs."""
        self.firsts = self.first.by_rid() if self.first is not None else {}
        self.first = None
        del self.fe, self.params
        gc.collect()

    def check(self, *, lower: str | None = None):
        """Compare a seeded sample of the served requests with the
        reference, at the precision the configuration states.  The sample
        holds the longest finished request, one of each prefill bucket
        the traffic reaches, then random ones up to `sample_tokens`
        served tokens.  Numbers:
          first_logit_nmse  mean over the sample of the mean squared gap
                            between the served first-token logits and
                            the reference's, over the reference logits'
                            variance (prefill, chunked prefill, inject)
          first_logit_err   the same with |gap| over the std
          mean_gap          mean, over every served token, of the gap by
                            which its reference logit lies below the
                            reference's best, over the logits' mean std
                            (decode through the paged cache)
          widest_gap        the largest such gap
        and short_outputs, the measured requests that did not end served
        with their whole token budget.  Returns (numbers, control's
        numbers or None): with `lower`, the reference computed in that
        precision is put in the program's place, on the same prompts and
        tokens, and read the same way."""
        cc = self.config["correctness"]
        done = [r for r in self.records
                if r.status == "served" and r.tokens is not None
                and len(r.tokens)]
        short = sum(1 for r in self.records if r.measured and (
            r.status != "served" or r.tokens is None
            or len(r.tokens) != r.max_new))
        firsts = self.firsts
        done = [r for r in done if r.rid in firsts]
        rng = sched_gen.rng_for(self.seed, 2)
        order = list(rng.permutation(len(done)))
        pick = []
        if done:
            pick.append(max(range(len(done)), key=lambda j: (
                len(done[j].prompt) + len(done[j].tokens), -j)))
        strata = {}
        for j in order:
            strata.setdefault(self._bucket(done[j]), j)
        pick += [j for j in strata.values() if j not in pick]
        n_tok = sum(len(done[j].tokens) for j in pick)
        for j in order:
            if n_tok >= cc["sample_tokens"]:
                break
            if j not in pick:
                pick.append(j)
                n_tok += len(done[j].tokens)
        seqs = [(done[j].prompt, done[j].tokens, firsts[done[j].rid])
                for j in pick]
        pad = -(-self.max_len // 256) * 256
        res = self.ref.compare(self.model, sched_gen.seed32(self.seed, 0),
                               seqs, pad_to=pad,
                               precision=cc["reference_precision"],
                               lower=lower)
        self.compared = {"requests": len(seqs),
                         "tokens": int(sum(len(r["gap"]) for r in res))}
        ours = _numbers(res, "", short)
        theirs = _numbers(res, "control_", short) if lower else None
        self.last = (ours, theirs)
        return ours, theirs

    def _bucket(self, r: Record) -> int:
        b = [x for x in self.sched_cfg.buckets if len(r.prompt) <= x]
        return b[0] if b else -1


def _numbers(res: list, pre: str, short: int) -> dict:
    """The compared numbers of one side (program, or control with
    pre="control_") from the reference's per-request readings.  With no
    request to compare, the precision numbers read inf and fail."""
    if not res:
        return {"first_logit_err": math.inf, "first_logit_nmse": math.inf,
                "mean_gap": math.inf, "widest_gap": math.inf,
                "short_outputs": short}
    scale = float(np.mean(np.concatenate([r["std"] for r in res])))
    gaps = np.concatenate([r[pre + "gap"] for r in res])
    first = np.asarray([r[pre + "first"] for r in res])
    sd0 = np.asarray([r["std"][0] for r in res])
    return {"first_logit_err": float(np.mean(first[:, 0] / sd0)),
            "first_logit_nmse": float(np.mean(first[:, 1] / sd0 ** 2)),
            "mean_gap": float(gaps.mean()) / scale,
            "widest_gap": float(gaps.max()) / scale,
            "short_outputs": short}


def _gauges(tel) -> dict:
    return {"backlog": tel.metrics.gauge("sched.backlog").value}


System = LMServing
