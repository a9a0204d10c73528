"""Read the numbers that decide `correct` for a cell on many seeds, with
the control beside them, in this one process on the chip: the program's
readings set the lower end of each limit, the control's (the reference
computed one precision lower: bfloat16 for float32) the upper end.
With several `--precisions`, the same served requests are also scored
against the reference at each of those matmul precisions.

  python3 chipbench/tools/calibrate.py --workload qwen2-0.5b.prefill-mix \\
      --seeds 101,102,103 --seconds 20
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

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--lower", default="bfloat16")
    ap.add_argument("--precisions", default="",
                    help="further reference precisions, comma-separated")
    a = ap.parse_args()
    for seed in [int(x) for x in a.seeds.split(",")]:
        keep = {}

        def tamper(system):
            keep["s"] = system

        def log(*x):
            print(*x, file=sys.stderr, flush=True)

        res = run.run_cell(a.workload, seed, a.seconds, False, tamper=tamper,
                           lower=a.lower, log=log)
        s = keep["s"]
        prec = s.config["correctness"]["reference_precision"]
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "failed": res["failed"],
               "compared": s.compared, "metrics": res["metrics"],
               "memory_peak_bytes": res["device"]["memory_peak_bytes"],
               "by_precision": {prec: {"program": s.last[0],
                                       "control": s.last[1]}}}
        for p in [x for x in a.precisions.split(",") if x and x != prec]:
            t = time.monotonic()
            s.config["correctness"]["reference_precision"] = p
            ours, theirs = s.check(lower=a.lower)
            row["by_precision"][p] = {"program": ours, "control": theirs,
                                      "reference_s": time.monotonic() - t}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
