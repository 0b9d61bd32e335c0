"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

The window is what the harness's own host spans cover on the profiler's
clock: from the first `bench.dispatch` span's start to the last
`bench.fetch` span's end.  Device work is the "XLA Ops" line of each
`/device:TPU:<i>` plane.  Busy time is the union of its op intervals inside
the window; a kernel is an op whose HLO is a `tpu_custom_call` (a Pallas /
Mosaic kernel); an op's self time is its time less that of the ops nested
in it (a `cond` or `while` holds the ops it runs).  Each idle gap of the
device is put down to the harness span the host was in at its middle.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

SPAN_DISPATCH = "bench.dispatch"
SPAN_FETCH = "bench.fetch"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    # The harness's own spans only: the runtime's many host events (level
    # 2) and Python calls cost host time inside the window.
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


@dataclass
class Summary:
    window_ns: float
    busy_ns: list                      # per device plane used
    kernel_ns: list                    # per device plane used
    kernel_events: list                # per device plane used: count
    op_self_ns: dict = field(default_factory=dict)   # op name -> ns
    idle_by_span: dict = field(default_factory=dict)  # host span -> ns

    @property
    def busy_s(self) -> float:
        return float(np.mean(self.busy_ns)) / 1e9 if self.busy_ns else 0.0

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_self_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def op_name(event_name: str) -> str:
    """`%fusion.19 = u32[...] fusion(...)` -> `fusion.19`."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def union(intervals) -> list:
    """Sorted, merged (start, end) pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events) -> dict:
    """events: (start, end, name) on one line, nested or disjoint.
    Returns name -> self time."""
    acc: dict = collections.defaultdict(float)
    stack: list = []                  # [end, name, child time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            end, n, child = stack.pop()
            acc[n] -= child
        if stack:
            stack[-1][2] += e - s
        acc[name] += e - s
        stack.append([e, name, 0.0])
    while stack:
        _, n, child = stack.pop()
        acc[n] -= child
    return dict(acc)


def _host_spans(pd, names):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return sorted(spans)


def reduce(pd, devices: int) -> Summary:
    """Reduce a trace of the harness's window over `devices` chips."""
    spans = _host_spans(pd, (SPAN_DISPATCH, SPAN_FETCH))
    starts = [s for s, _, n in spans if n == SPAN_DISPATCH]
    ends = [e for _, e, n in spans if n == SPAN_FETCH]
    if not starts or not ends:
        raise RuntimeError("the trace holds none of the harness's spans")
    w0, w1 = min(starts), max(ends)
    planes = sorted((int(m.group(1)), p) for p in pd.planes
                    if (m := DEVICE_PLANE.match(p.name)))[:devices]
    busy, kern, kcount = [], [], []
    op_self: dict = collections.defaultdict(float)
    merged_all = []
    for _, plane in planes:
        events = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    events.append((s, e, ev.name))
        merged = union((s, e) for s, e, _ in events)
        merged_all.append(merged)
        busy.append(sum(e - s for s, e in merged))
        ks = [(s, e) for s, e, n in events if KERNEL_MARK in n]
        kern.append(sum(e - s for s, e in union(ks)))
        kcount.append(len(ks))
        for name, t in self_times((s, e, op_name(n))
                                  for s, e, n in events).items():
            op_self[name] += t / len(planes)
    idle: dict = collections.defaultdict(float)
    if merged_all:
        for g0, g1 in _gaps(merged_all[0], w0, w1):
            idle[_span_at(spans, (g0 + g1) / 2)] += g1 - g0
    return Summary(window_ns=w1 - w0, busy_ns=busy, kernel_ns=kern,
                   kernel_events=kcount, op_self_ns=dict(op_self),
                   idle_by_span=dict(idle))


def _gaps(merged, w0, w1):
    t = w0
    for s, e in merged:
        if s > t:
            yield t, s
        t = max(t, e)
    if w1 > t:
        yield t, w1


def _span_at(spans, t) -> str:
    """The harness span that holds time t (spans sorted and disjoint)."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return "host:" + spans[i][2]
    return "host:bench.loop"
