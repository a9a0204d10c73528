"""Find the knee of an open-loop cell: run its traffic at each of a list
of rates, in this one process on the chip, and print for each the
backlog and the median time to first token in the first and last third
of the window.  The knee is the highest rate whose backlog does not grow
over the window.

  python3 chipbench/tools/sweep.py --workload qwen2-0.5b.prefill-mix \\
      --rates 1,2,3,4 --seconds 20 --seeds 1,2
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import run  # noqa: E402
import stats  # noqa: E402


def thirds(vals):
    n = len(vals)
    return vals[: max(1, n // 3)], vals[-max(1, n // 3):]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seeds", default="1")
    a = ap.parse_args()
    runs = [(float(r), int(s)) for s in a.seeds.split(",")
            for r in a.rates.split(",")]
    for rate, seed in runs:
        keep = {}

        def tamper(system):
            keep["s"] = system
            system.tel.enabled = True      # sample the backlog gauge

        res = run.run_cell(a.workload, seed, a.seconds, False,
                           mix_override={"rate_per_s": rate}, tamper=tamper,
                           log=lambda *x: print(*x, file=sys.stderr))
        s = keep["s"]
        recs = sorted((r for r in s.records if r.measured),
                      key=lambda r: r.t_due)
        tt = stats.ttft_values_ms(recs)
        first, last = thirds(tt)
        g = [x["backlog"] for x in s.gauges]
        b0, b1 = thirds(g) if g else ([0], [0])
        t_end = s.t_end
        print(json.dumps({
            "rate": rate, "seed": seed, "requests": len(recs),
            "finished_in_window": sum(r.t_done is not None
                                      and r.t_done <= t_end
                                      for r in s.records),
            "ttft_p50_first_third_ms": stats.percentile(first, 50),
            "ttft_p50_last_third_ms": stats.percentile(last, 50),
            "ttft_p95_ms": stats.percentile(tt, 95),
            "backlog_mean_first_third": sum(b0) / len(b0),
            "backlog_mean_last_third": sum(b1) / len(b1),
            "metrics": res["metrics"], "correct": res["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
