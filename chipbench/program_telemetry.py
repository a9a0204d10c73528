"""Readings of the program's own telemetry in a traced run.

The program (`serve/telemetry.py`, enabled in `--trace 1` runs) records,
besides its host spans:

  * per request, on track `req <rid>`, its time to first token split
    where it is spent: `queue_wait` (frontend), `sched_queue` (scheduler
    submit to slot acquired), `staging` (slot acquired to inject
    dispatched), `first_token_lag` (inject dispatched to the first
    token), under a parent `ttft`;
  * on each `decode_chunk` span, the rows that chunk decodes (`rows`);
  * in the profiler trace, each host span as `<track>/<name>`
    (`scheduler/round`, `frontend/step`, ...).

Every reader returns None where the program records none of this."""
from __future__ import annotations

import glob
import os
import time

import stats
import trace_reduce

TAIL_Q = 75                      # the tail: TTFT at or above this percentile
PROGRAM_PREFIXES = ("scheduler/", "frontend/")


def part_s(tel, part: str) -> dict:
    """{frontend request id: seconds} of one TTFT part."""
    return {int(s.track[4:]): s.dur for s in tel.trace.spans
            if s.name == part and s.track.startswith("req ")}


def tail(records, until=None) -> list:
    """The measured requests whose time to first token ranks at or above
    the TAIL_Q-th percentile of the window's; with `until`, of those
    whose first token came before it."""
    recs = [r for r in records if r.measured and (
        until is None or (r.t_first is not None and r.t_first < until))]
    if not recs:
        return []
    vals = stats.ttft_values_ms(recs)
    cut = stats.percentile(vals, TAIL_Q)
    return [r for r, v in zip(recs, vals) if v >= cut]


def traced_until(ctx):
    """In a traced run, the window's end: stopping the profiler there
    holds the host for seconds, and a request still waiting for its first
    token then waits through the stall.  The tail is taken over the
    requests answered before it.  None in an untraced run."""
    return ctx.system.t_end if ctx.trace is not None else None


def tail_mean_ms(ctx, part: str):
    """Mean of one TTFT part over the tail's requests that recorded it."""
    tel = ctx.tel
    if tel is None or not tel.enabled:
        return None
    got = part_s(tel, part)
    vals = [got[r.rid] for r in tail(ctx.system.records, traced_until(ctx))
            if r.rid in got]
    return 1e3 * sum(vals) / len(vals) if vals else None


def decode_row_use(ctx):
    """Rows decoded over rows attempted (slots x chunks) by the decode
    chunks dispatched in the window, in %."""
    tel, sys_ = ctx.tel, ctx.system
    if tel is None or not tel.enabled:
        return None
    off = time.monotonic() - tel.clock()      # telemetry clock -> window's
    rows = [s.args["rows"] for s in tel.trace.spans
            if s.name == "decode_chunk" and "rows" in s.args
            and sys_.t0 <= s.t0 + off < sys_.t_end]
    if not rows:
        return None
    slots = ctx.config["serving"]["scheduler"]["max_slots"]
    return 100.0 * sum(rows) / (len(rows) * slots)


def idle_split(log_dir: str) -> dict:
    """Device idle seconds of the traced slice (the harness's
    `bench/traced` span) charged to the innermost host span covering a
    gap's middle, of the program's spans and the harness's `bench/` ones
    together: a program span wins inside the harness span that holds it.
    Names keep their prefix; gaps under 20 us go to "(between ops)"."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    device_lines, host, window = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith("/device:TPU:") and \
                name[len("/device:TPU:"):].isdigit():
            ops = [("op", int(ev.start_ns), int(ev.duration_ns))
                   for line in plane.lines
                   if line.name == trace_reduce.OPS_LINE
                   for ev in line.events]
            device_lines[name] = {"ops": ops, "modules": []}
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES + (
                            trace_reduce.HOST_PREFIX,)):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), ev.name))
                        if ev.name == "bench/" + trace_reduce.WINDOW_SPAN:
                            window = (s, s + int(ev.duration_ns))
    if window is None or not device_lines:
        return {}
    return trace_reduce.reduce_events(device_lines, host, *window).idle_by_host


def idle_by_program(split: dict) -> dict:
    """The program spans' part of an `idle_split`."""
    return {k: v for k, v in split.items() if k.startswith(PROGRAM_PREFIXES)}
