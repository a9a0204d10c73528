"""Reduce a JAX profiler trace to the benchmark's device numbers.

Busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each `/device:TPU:<n>` plane), averaged
over the devices; idle share is 1 - busy / window.  Per-op and
per-program device time sum the events' durations by name.  Idle gaps
are the holes in a device's busy union; each is charged to the
innermost of the harness's own `jax.profiler.TraceAnnotation` spans
(names starting with "bench/") that covers the gap's middle on the host,
and holes under 20 us, between back-to-back operations, to
"(between ops)".
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench/"
WINDOW_SPAN = "traced"            # the harness wraps the traced slice in it


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # traced window (host clock)
    busy_s: float                   # mean over devices of the busy union
    n_devices: int
    op_s: dict                      # op name -> device seconds (all devices)
    module_s: dict                  # program name -> device seconds
    idle_by_host: dict              # host span name -> idle seconds
    longest_gaps: list              # [(host span name, seconds)], longest first
    op_text: dict = dataclasses.field(default_factory=dict)
                                    # custom-call op name -> its HLO text

    def op_seconds(self, needle: str) -> float:
        """Device seconds of the ops whose name or HLO text holds
        `needle` (a kernel's name)."""
        return sum(v for k, v in self.op_s.items()
                   if needle in k or needle in self.op_text.get(k, ""))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, t0, t1) -> list:
    """Holes of a sorted disjoint busy union inside [t0, t1]."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


SHORT_GAP_NS = 20_000               # gaps between back-to-back ops


class HostSpans:
    """Innermost-span lookup over properly nested host spans: of the spans
    covering an instant, the innermost is the one that started last."""

    def __init__(self, spans):
        # of spans that start together, the outer one sorts first
        self.spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0:
            s, e, name = self.spans[i]
            if e >= t:
                return name
            i -= 1
        return "(no bench span)"


def reduce_events(device_lines, host_spans, t0_ns, t1_ns,
                  op_text=None) -> TraceSummary:
    """device_lines: {device: {"ops": [(name, start_ns, dur_ns)],
    "modules": [...]}}; host_spans: [(start_ns, end_ns, name)];
    [t0_ns, t1_ns] the traced window."""
    op_s, module_s = defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    all_gaps = []
    busy_total = 0.0
    host = HostSpans(host_spans)
    for lines in device_lines.values():
        iv = []
        for name, s, d in lines["ops"]:
            # a control-flow op spans the ops of its body, which have
            # events of their own: busy, but not counted again by name
            if not name.split(".")[0] in CONTAINERS:
                op_s[name] += d * 1e-9
            iv.append((max(s, t0_ns), min(s + d, t1_ns)))
        for name, s, d in lines["modules"]:
            module_s[name] += d * 1e-9
        busy = union((s, e) for s, e in iv if e > s)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for s, e in gaps(busy, t0_ns, t1_ns):
            name = ("(between ops)" if e - s < SHORT_GAP_NS
                    else host.at((s + e) / 2))
            idle[name] += (e - s) * 1e-9
            all_gaps.append((name, (e - s) * 1e-9))
    n = max(1, len(device_lines))
    all_gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(t1_ns - t0_ns) * 1e-9,
                        busy_s=busy_total / n, n_devices=len(device_lines),
                        op_s=dict(op_s), module_s=dict(module_s),
                        idle_by_host=dict(idle), longest_gaps=all_gaps[:10],
                        op_text=dict(op_text or {}))


def _base_name(name: str) -> str:
    """Program names carry a numeric suffix per compile ("jit_f(12)")."""
    return name.split("(")[0]


CONTAINERS = ("while", "conditional", "call")


_KERNEL = re.compile(r'"?(?:kernel_name|name)"?\s*[:=]\s*\\?"([A-Za-z_][\w.]*)')


def _op_name(name: str) -> str:
    """An op event is named by its HLO text ("%fusion.3 = f32[...] ...");
    keep the instruction's name, and for a custom call (a Pallas kernel)
    the kernel's name where the text gives it."""
    short = name.split(" = ")[0].lstrip("%")
    if short.startswith("custom-call"):
        m = _KERNEL.search(name)
        if m:
            return f"{short}:{m.group(1)}"
    return short


def load(log_dir: str, window_s: float) -> TraceSummary:
    """Read the newest `.xplane.pb` under log_dir.  The traced window is
    the harness's "bench/traced" span; without one, the span of the
    events read, at least `window_s` long, ending at the last event."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    device_lines, host_spans, op_text = {}, [], {}
    lo, hi = None, None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    name = (_op_name(ev.name) if key == "ops"
                            else _base_name(ev.name))
                    if name.startswith("custom-call"):
                        op_text.setdefault(name, ev.name[:4000])
                    lines[key].append((name, s, d))
                    lo = s if lo is None else min(lo, s)
                    hi = s + d if hi is None else max(hi, s + d)
            device_lines[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s, d = int(ev.start_ns), int(ev.duration_ns)
                        host_spans.append((s, s + d, ev.name[len(HOST_PREFIX):]))
    if hi is None:
        raise ValueError("the trace holds no device operation")
    window = [(s, e) for s, e, name in host_spans if name == WINDOW_SPAN]
    if window:
        t0, t1 = window[0]
    else:
        t1 = hi
        t0 = min(lo, t1 - int(window_s * 1e9))
    return reduce_events(device_lines, host_spans, t0, t1, op_text)


def describe(log_dir: str, top: int = 25) -> None:
    """Print the planes and lines of a trace and the busiest event names
    of each line: the first look to take before changing the reduction."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            tot, cnt = defaultdict(float), defaultdict(int)
            for ev in line.events:
                tot[ev.name] += ev.duration_ns * 1e-9
                cnt[ev.name] += 1
            print(f"  line {line.name!r}: {sum(cnt.values())} events")
            for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                print(f"    {s:12.6f}s {cnt[name]:7d}  {name[:150]}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1])
