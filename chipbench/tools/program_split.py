"""Run a cell in this one process on the chip, as chipbench/run.py does,
and print beside each result what the program's own telemetry says:

  with trace 1  the traced slice's device idle time charged to the
                program's host spans (`scheduler/...`, `frontend/...`)
                beside the harness's `bench/` charging, and the tail's
                time to first token split into its parts;
  with trace 0  the host's wall and CPU milliseconds per scheduling
                round of the window, with the program's telemetry off,
                or on without a profiler (telemetry 1), and with it on
                the tail's split as above.

  python3 chipbench/tools/program_split.py --workload qwen2-0.5b.prefill-mix \\
      --runs 101:1 102:0:0 102:0:1

Each run is `seed:trace[:telemetry]`; one JSON line per run on standard
output.  Set-up after the first run is not the cell's: the process has
compiled already.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import program_telemetry as pt  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

PARTS = ("queue_wait", "sched_queue", "staging", "first_token_lag", "ttft")


def tail_split(system, until=None) -> dict:
    """Mean ms over the tail of its measured TTFT (due to first token), of
    the generator's lateness (due to submitted), and of each TTFT part
    and their parent span (`ttft_ms`: submitted to first token)."""
    tail = pt.tail(system.records, until)
    out = {"tail_n": len(tail)}
    if not tail:
        return out
    out["measured_ttft_ms"] = sum((r.t_first - r.t_due) * 1e3 for r in tail
                                  if r.t_first is not None) / len(tail)
    out["late_ms"] = sum((r.t_submit - r.t_due) * 1e3 for r in tail) \
        / len(tail)
    for part in PARTS:
        got = pt.part_s(system.tel, part)
        vals = [got[r.rid] for r in tail if r.rid in got]
        if vals:
            out[f"{part}_ms"] = 1e3 * sum(vals) / len(vals)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50)
    a = ap.parse_args()

    def log(*x):
        print(*x, file=sys.stderr, flush=True)

    load = trace_reduce.load
    for spec in a.runs:
        seed, trace, tel_on = (list(map(int, spec.split(":"))) + [0])[:3]
        keep, rounds = {}, []

        def split_load(log_dir, window_s):
            keep["idle"] = pt.idle_split(log_dir)
            return load(log_dir, window_s)

        def tamper(system):
            keep["s"] = system
            if tel_on:
                system.tel.enabled = True
            step = system.fe.step

            def timed():
                t, c = time.monotonic(), time.thread_time()
                out = step()
                rounds.append((t, time.monotonic() - t,
                               time.thread_time() - c))
                return out

            system.fe.step = timed

        trace_reduce.load = split_load
        try:
            res = run.run_cell(a.workload, seed, a.seconds, bool(trace),
                               tamper=tamper, log=log)
        finally:
            trace_reduce.load = load
        s = keep["s"]
        win = [(w, c) for t, w, c in rounds if s.t0 <= t < s.t_end]
        row = {"seed": seed, "trace": trace, "telemetry": tel_on or trace,
               "result": run._finite(res),
               "rounds": len(win),
               "round_wall_ms": 1e3 * sum(w for w, _ in win) / len(win),
               "round_cpu_ms": 1e3 * sum(c for _, c in win) / len(win)}
        if trace or tel_on:
            row["tail"] = tail_split(s, s.t_end if trace else None)
        if trace:
            prog = pt.idle_by_program(keep.get("idle", {}))
            bench = dict(res.get("breakdown", {}).get("idle_gaps", []))
            row["idle_by_program"] = dict(sorted(prog.items(),
                                                 key=lambda kv: -kv[1]))
            row["idle_frontend_step_s"] = bench.get("frontend.step")
            row["idle_program_s"] = sum(prog.values())
            log("idle by program span: " + json.dumps(row["idle_by_program"]))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
