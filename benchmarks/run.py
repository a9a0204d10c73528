"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Figure benchmarks train a small
synthetic-data AgileNN system once (cached) and reuse it; the roofline
table reads the dry-run JSON dumps if present.

  PYTHONPATH=src python -m benchmarks.run                 # everything
  PYTHONPATH=src python -m benchmarks.run --only fig16,tab2
  PYTHONPATH=src python -m benchmarks.run --only kernels,serve \
      --json BENCH_kernels.json                           # perf baseline
  PYTHONPATH=src python -m benchmarks.run --json B.json --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated figure keys (fig16..fig24, tab2, "
                         "kernels, serve, serve_sharded, gateway, faults, "
                         "prefix, stream, recovery, telemetry, roofline)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump the collected rows as a JSON baseline")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: cheap suites only (kernels, serve, "
                         "gateway, faults, prefix, stream, recovery, "
                         "telemetry) with shrunk workloads")
    ap.add_argument("--compare", default=None, metavar="BASELINE",
                    help="regression gate: compare collected rows against a "
                         "JSON baseline and exit 2 if any matching row "
                         "regresses by more than 25%% (machine-speed "
                         "normalized; rows whose derived string differs are "
                         "skipped as incomparable workloads)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import benchmarks.common
    if args.smoke:
        benchmarks.common.SMOKE = True

    from benchmarks.ablations import ABLATIONS
    from benchmarks.faults import faults_rows
    from benchmarks.gateway import gateway_rows
    from benchmarks.kernel_micro import kernel_micro_rows
    from benchmarks.paper_figures import ALL_FIGURES
    from benchmarks.prefix_cache import prefix_cache_rows
    from benchmarks.recovery import recovery_rows
    from benchmarks.roofline_table import roofline_rows
    from benchmarks.serve_sharded import serve_sharded_rows
    from benchmarks.serve_steady import serve_steady_rows
    from benchmarks.stream_slo import stream_slo_rows
    from benchmarks.telemetry_bench import telemetry_rows

    suites = dict(ALL_FIGURES)
    suites.update(ABLATIONS)
    suites["kernels"] = kernel_micro_rows
    suites["serve"] = serve_steady_rows
    suites["serve_sharded"] = serve_sharded_rows
    suites["gateway"] = gateway_rows
    suites["faults"] = faults_rows
    suites["prefix"] = prefix_cache_rows
    suites["stream"] = stream_slo_rows
    suites["recovery"] = recovery_rows
    suites["telemetry"] = telemetry_rows
    suites["roofline"] = roofline_rows

    if args.only:
        selected = args.only.split(",")
    elif args.smoke:
        # serve_sharded is not in the default smoke set: its rows pin the
        # device topology, and only the multi-device CI job (forced
        # 8-device mesh, --only serve_sharded) has baseline rows to match
        selected = ["kernels", "serve", "gateway", "faults", "prefix",
                    "stream", "recovery", "telemetry"]
    else:
        selected = list(suites)
    print("name,value,derived")
    failed = 0
    collected = []
    for key in selected:
        if key not in suites:
            failed += 1
            print(f"{key},ERROR,unknown suite", flush=True)
            continue
        try:
            for name, value, derived in suites[key]():
                collected.append({"name": name, "value": value,
                                  "derived": derived})
                if isinstance(value, float):
                    value = f"{value:.6g}"
                print(f"{name},{value},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{key},ERROR,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(limit=3, file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"suites": selected, "rows": collected}, f, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)
    if failed:
        sys.exit(1)
    if args.compare and compare_rows(collected, args.compare):
        sys.exit(2)


def compare_rows(collected: list, baseline_path: str) -> list:
    """Gate collected rows against a baseline; returns the regressions.

    A row is comparable when the baseline holds the same name AND the
    same derived string (the derived text pins the workload — a smoke-
    sized serve row must not be judged against the full-queue baseline).
    Lower-is-better rows (us / ms suffixes) regress when they grow >25%
    over baseline; throughput rows (tokens_per_s) when they shrink >25%.
    Wall-clock ratios are normalized by their median baseline/current
    speed ratio so a uniformly slower CI box doesn't trip the gate —
    only a row that regresses relative to the rest of the fleet does.
    Rows whose derived string ends in "simulated" are deterministic
    model outputs (the gateway's seeded fleet): machine speed cannot
    move them, so they are excluded from the median and gated
    symmetrically on their raw ratio — a >25% drift in EITHER direction
    is a semantic change to the simulation (an intentional one ships a
    regenerated baseline).  Rows ending in "_pct" are *already* ratios
    (telemetry overhead as a percentage of the uninstrumented drain):
    machine speed cancels out of them, so instead of baseline-ratio math
    they are gated against an absolute ceiling — >= 5% fails outright.
    """
    with open(baseline_path) as f:
        base = {r["name"]: r for r in json.load(f)["rows"]}
    pairs = []
    pct_fails = []
    for row in collected:
        b = base.get(row["name"])
        if (b is None or b.get("derived") != row["derived"]
                or not isinstance(row["value"], (int, float))
                or not isinstance(b["value"], (int, float))):
            continue
        name = row["name"]
        if name.endswith("_pct"):
            if row["value"] >= 5.0:
                pct_fails.append((name, float(row["value"])))
            continue
        if not b["value"] or not row["value"]:
            continue
        lower_better = name.endswith(".us") or name.endswith("_ms") \
            or name.endswith(".ms")
        higher_better = "per_s" in name
        deterministic = str(row["derived"]).endswith("simulated")
        if deterministic:
            # any drift is semantic: direction doesn't matter
            ratio = max(row["value"] / b["value"],
                        b["value"] / row["value"])
        elif lower_better or higher_better:
            # slowdown ratio > 1 means this row got slower than baseline
            ratio = (row["value"] / b["value"] if lower_better
                     else b["value"] / row["value"])
        else:
            continue
        pairs.append((name, ratio, deterministic))
    for n, v in pct_fails:
        print(f"REGRESSION {n}: {v:.2f}% >= 5% absolute ceiling",
              file=sys.stderr)
    if not pairs:
        if not pct_fails:
            print(f"compare: no comparable rows in {baseline_path}",
                  file=sys.stderr)
        return pct_fails
    walls = sorted(r for _, r, det in pairs if not det) \
        or sorted(r for _, r, _ in pairs)
    mid = len(walls) // 2                          # machine-speed median:
    scale = (walls[mid] if len(walls) % 2          # a true median, so an
             else (walls[mid - 1] + walls[mid]) / 2)    # even-count list
    # can't adopt an upper-middle regression as the machine speed
    # wall-clock rows must fail both tests: the raw ratio (the row
    # actually got slower) and the normalized one (slower than the fleet
    # explains) — a row whose absolute time never grew is not a
    # regression just because the CI box runs its neighbours faster.
    # deterministic rows fail on raw ratio alone.
    regressions = [(n, r, r if det else r / scale) for n, r, det in pairs
                   if r > 1.25 and (det or r / scale > 1.25)]
    for n, raw, rel in regressions:
        print(f"REGRESSION {n}: {raw:.2f}x slower than baseline "
              f"({rel:.2f}x after machine normalization)", file=sys.stderr)
    if not regressions and not pct_fails:
        print(f"compare: {len(pairs)} rows within 25% of {baseline_path} "
              f"(median speed ratio {scale:.2f})", file=sys.stderr)
    return regressions + pct_fails


if __name__ == "__main__":
    main()
