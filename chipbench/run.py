"""Chip benchmark of the AgileNN split-inference stack.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chip it is started on, in this one
process (it starts no child).  The cell names a configuration
(`configs/<config>.json`, its plain reference `configs/<config>.py`,
and the serving system it runs, `systems/<system>.py`) and a traffic mix
(`traffic/<mix>.json`); each metric is read by `metrics/<metric>.py`.  With --trace 0 the last line of standard output
reports the cell's end-to-end metrics, with --trace 1 its per-layer
metrics from a profiler trace of a slice of the window.  The numbers
that decide `correct` are printed, each beside its limit, as the last
lines of standard error and under "checks", the result's last key.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the repository's program is not
beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_S = 10.0           # length of the traced slice in a --trace 1 run
TRACE_DIR = os.path.join(ROOT, ".chipbench_tmp", "trace")

sys.path.insert(0, HERE)


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, kind: str) -> list:
    """The metrics a cell reports: end-to-end ones listed for it (or
    for every cell), per-layer ones listed for it (or, unlisted, for
    every cell reporting the end-to-end metric they move)."""
    name = cell["name"]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if kind == "end_to_end":
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


class Profiler:
    """Traces one slice of the window: its last `TRACE_S` seconds.
    Stopping the profiler holds the host for seconds while it collects
    the trace; at the window's end that stall falls after every reading
    taken in the window."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.start_at = max(0.0, seconds - TRACE_S)
        self.stop_at = seconds
        self.state = "idle"
        self.ann = None
        self.t = [None, None]        # the traced slice, host clock
        self.released = None         # when the host got back from the stop

    def poll(self, elapsed: float) -> None:
        import jax
        if not self.enabled:
            return
        if self.state == "idle" and elapsed >= self.start_at:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.ann = jax.profiler.TraceAnnotation("bench/traced")
            self.ann.__enter__()
            self.t[0] = time.monotonic()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_at:
            self._stop()

    def _stop(self) -> None:
        import jax
        self.t[1] = time.monotonic()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.released = time.monotonic()
        self.state = "done"

    def finish(self) -> None:
        if self.state == "on":
            self._stop()

    @property
    def window_s(self) -> float:
        return self.t[1] - self.t[0]


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache."""

    def __init__(self):
        import jax
        self.n = 0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def check_chip(chips: int) -> "object":
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, config_override=None,
             mix_override=None, tamper=None, lower: str | None = None,
             log=print) -> dict:
    """One run of one cell; returns the result object.  Tests pass
    `require_chip=False` with small overrides; `tamper(system)` breaks
    the timed path underneath (for the check's own test); `lower` also
    reads the control (the reference in that precision put in the
    program's place) and judges it by the same limits."""
    bench = load_json("..", "BENCHMARK.json")
    cell = find_cell(bench, workload)
    import jax
    if require_chip:
        devs = check_chip(cell["chips"])
    else:
        devs = jax.devices()
    cache_dir = None
    if require_chip:
        from repro.launch.compile_cache import use_compile_cache
        cache_dir = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()

    from repro.serve.telemetry import Telemetry
    import peaks as peaks_mod
    config = load_json("configs", f"{cell['config']}.json")
    if config_override:
        config = _merge(config, config_override)
    mix = load_json("traffic", f"{cell['traffic']}.json")
    if mix_override:
        mix = _merge(mix, mix_override)
    ref = load_module(os.path.join(HERE, "configs", f"{cell['config']}.py"),
                      f"ref_{cell['config'].replace('-', '_').replace('.', '_')}")
    system_cls = load_module(
        os.path.join(HERE, "systems", f"{config['system']}.py"),
        f"system_{config['system']}").System

    tel = Telemetry(enabled=bool(trace))
    system = system_cls(config, ref, mix, seed, seconds, tel)
    system.warm_up()
    if tamper is not None:
        tamper(system)
    setup_s = time.monotonic() - T_PROCESS
    log(f"set-up {setup_s:.3f}s; compile cache {cache_dir}; "
        f"compiles {compiles.n} (persistent hits {compiles.hits}, "
        f"misses {compiles.misses})")

    if trace:
        tel.trace.spans.clear()
    n_before = compiles.n
    profiler = Profiler(bool(trace), seconds)
    system.run_window(profiler)
    window_compiles = compiles.n - n_before
    log(f"window: {json.dumps(system.describe())}")
    dev = devs[0]
    stats = dev.memory_stats() or {}
    peak_mem = int(stats.get("peak_bytes_in_use", 0))

    summary = None
    if trace:
        import trace_reduce
        summary = trace_reduce.load(TRACE_DIR, profiler.window_s)
        top = sorted(summary.module_s.items(), key=lambda kv: -kv[1])[:8]
        log(f"traced {summary.window_s:.3f}s, busy {summary.busy_s:.3f}s; "
            f"programs {json.dumps(top)}; longest idle gaps "
            f"{json.dumps(summary.longest_gaps[:5])}")
        shutil.rmtree(os.path.dirname(TRACE_DIR), ignore_errors=True)

    ctx = types.SimpleNamespace(
        cell=cell, config=config, mix=mix, system=system, tel=tel,
        seconds=seconds, setup_s=setup_s, trace=summary,
        trace_t=profiler.t, peaks=(peaks_mod.peaks_for(dev.device_kind)
                                   if require_chip else None))
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, cell, kind):
        reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                             "metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    system.release()
    checks, control = system.check(lower=lower)
    correct, compared = judge(config, checks)
    attempted = sum(r.measured for r in system.records)
    failed = sum(r.measured and r.status != "served"
                 for r in system.records)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_mem}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in summary.top_idle(10)]}
    info = {k: v for k, v in checks.items() if k not in compared}
    log(f"window compiles {window_compiles}; compared "
        f"{json.dumps(system.compared)}; not compared {json.dumps(info)}")
    if control is not None:
        c_correct, c_compared = judge(config, control)
        result["control"] = {"precision": lower, "correct": c_correct,
                             "checks": c_compared,
                             "numbers": control}
    result["checks"] = compared
    return result


def judge(config: dict, numbers: dict) -> tuple:
    """Hold each compared number to its limit: (correct, {name: {"value",
    "limit"}}).  The configuration states the limit of each inexact
    number (`limit_<name>`); short outputs are exact, with limit 0.  A
    number that reads inf or NaN fails.  A configuration that states no
    limit for a number leaves it out of the comparison."""
    cc = config["correctness"]
    out = {}
    for k, v in numbers.items():
        lim = 0 if k == "short_outputs" else cc.get(f"limit_{k}")
        if lim is not None:
            out[k] = {"value": v, "limit": lim}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in out.values())
    return ok, out


def _finite(result: dict) -> dict:
    """JSON has no infinity: a metric that reads inf (a tail that lands
    on a request never answered) is left out, and a compared number that
    reads inf or NaN, which `judge` has failed, is written as null."""
    result["metrics"] = {k: v for k, v in result["metrics"].items()
                         if math.isfinite(v["value"])}
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = None
    return result


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and \
            isinstance(base.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print("chipbench: the repository's program (src/repro) is not "
              "beside the benchmark", file=sys.stderr)
        return 2

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), log=log)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    result = _finite(result)
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
