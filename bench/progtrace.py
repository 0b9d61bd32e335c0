"""The program's own spans and device scopes in a profiler trace.

`bench/devtrace.py` reduces a trace to what the harness's spans and the
device's ops show.  The program writes more into the same trace, on the
same clock:

  host spans  `atomics.apply` and its steps `.validate`, `.to_device` and
              `.launch` (`PROGRAM_SPANS`), nested in the harness's
              `bench.dispatch`.  A span's self time is its time inside
              the window less that of the spans nested in it.
  scopes      each device op of the round program names its layer: the
              first `engine.*` component of its op-name path.  A TPU
              trace keeps that path in the `tf_op` stat (`SCOPE_STAT`) of
              the op's event metadata, which `jax.profiler.ProfileData`
              does not show, so `op_paths` reads it from the `.xplane.pb`
              file itself.  A scope's time is the union of its ops'
              intervals.
  kernels     the Pallas kernels' events, counted by name
              (`engine_fast_round`, `engine_slow_round`).
  idle gaps   each part of a device gap goes to the innermost span, the
              harness's or the program's, that the host was in then
              (`host:<span>`; `host:bench.loop` where it was in none).

`readings` turns a reduction into the per-layer numbers a benchmark change
can add as metrics (PERF.md §7).  Run one traced cell as `bench/run.py`
runs it and print its result line with these added under `program`:

  python3 -m bench.progtrace --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys
from dataclasses import dataclass, field

from bench import devtrace

PROGRAM_SPANS = ("atomics.apply", "atomics.apply.validate",
                 "atomics.apply.to_device", "atomics.apply.launch")
SCOPE_STAT = "tf_op"
SCOPE_PREFIX = "engine."
HOST_LOOP = "bench.loop"


@dataclass
class ProgramSummary:
    batches: int                         # harness dispatch spans
    span_self_ns: dict = field(default_factory=dict)   # span -> ns
    scope_ns: dict = field(default_factory=dict)       # scope -> ns
    kernel_events_by_name: dict = field(default_factory=dict)  # -> count
    op_self_ns: dict = field(default_factory=dict)     # scope/op -> ns
    idle_by_span: dict = field(default_factory=dict)   # host:span -> ns

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_self_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def scope_of(op_path: str) -> str | None:
    """`jit(_apply_impl)/engine.commit/gather` -> `engine.commit`."""
    for part in op_path.split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


def kernel_name(event_name: str) -> str:
    """`%engine_fast_round.1 = (...) custom-call(...)` -> the kernel's
    name without XLA's instance suffix: `engine_fast_round`."""
    return re.sub(r"\.\d+$", "", devtrace.op_name(event_name))


def innermost(spans, w0: float, w1: float) -> list:
    """Spans (start, end, name), nested or disjoint, clipped to [w0, w1]
    -> sorted disjoint (start, end, name) pieces covering [w0, w1], each
    named by the innermost span over it (None where there is none)."""
    out: list = []
    stack: list = []                      # [end, name], innermost last
    t = w0

    def upto(u):
        nonlocal t
        if u > t:
            out.append((t, u, stack[-1][1] if stack else None))
            t = u

    clipped = [(max(s, w0), min(e, w1), n) for s, e, n in spans]
    for s, e, name in sorted(((s, e, n) for s, e, n in clipped if e > s),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            upto(stack[-1][0])
            stack.pop()
        upto(s)
        stack.append([e, name])
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(w1)
    return out


def split_gaps(gaps, pieces) -> dict:
    """Idle time of each gap (start, end) shared out over the innermost
    span pieces it overlaps: `host:<span>` -> ns."""
    acc: dict = collections.defaultdict(float)
    i = 0
    for g0, g1 in gaps:
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < g1:
            s, e, name = pieces[j]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                acc["host:" + (name or HOST_LOOP)] += overlap
            j += 1
    return dict(acc)


# ---------------------------------------------------------------------------
# Op-name paths from the trace file: the XSpace protobuf's wire format
# (xplane.proto of the profiler), read for the few fields needed.
# ---------------------------------------------------------------------------

def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of one message in buf[lo:hi]: an int for a
    varint, a (start, end) slice for a length-delimited field; fixed-width
    fields are skipped."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf, plane_fields, field_no: int):
    """The value messages of a map<int64, message> field of a plane."""
    for f, entry in plane_fields:
        if f == field_no:
            for g, value in _fields(buf, *entry):
                if g == 2:
                    yield value


def op_paths(buf) -> dict:
    """Serialized XSpace -> {device op event name: op-name path}, from the
    `tf_op` stat of each device plane's event metadata."""
    paths: dict = {}
    for f, plane in _fields(buf):
        if f != 1:                                   # XSpace.planes
            continue
        fields = list(_fields(buf, *plane))
        name = next((_text(buf, v) for g, v in fields if g == 2), "")
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}                              # XPlane.stat_metadata
        for meta in _map_values(buf, fields, 5):
            m = dict(_fields(buf, *meta))
            stat_names[m.get(1, 0)] = _text(buf, m[2]) if 2 in m else ""
        for meta in _map_values(buf, fields, 4):     # XPlane.event_metadata
            ev_name, path = None, None
            for g, v in _fields(buf, *meta):
                if g == 2:                           # XEventMetadata.name
                    ev_name = _text(buf, v)
                elif g == 5:                         # XEventMetadata.stats
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in stat:                    # XStat.str_value
                        path = _text(buf, stat[5])
                    elif 7 in stat:                  # XStat.ref_value
                        path = stat_names.get(stat[7], "")
            if ev_name is not None and path:
                paths[ev_name] = path.rstrip(":")
    return paths


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------

def _device_events(plane, w0, w1):
    """(start, end, event name) of the plane's XLA ops, clipped to the
    window."""
    out = []
    for line in plane.lines:
        if line.name != devtrace.OPS_LINE:
            continue
        for ev in line.events:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                out.append((s, e, ev.name))
    return out


def reduce(pd, devices: int, paths: dict) -> ProgramSummary:
    """Reduce a trace of the harness's window over `devices` chips;
    `paths` is `op_paths` of the same trace."""
    harness = devtrace._host_spans(pd, (devtrace.SPAN_DISPATCH,
                                        devtrace.SPAN_FETCH))
    starts = [s for s, _, n in harness if n == devtrace.SPAN_DISPATCH]
    ends = [e for _, e, n in harness if n == devtrace.SPAN_FETCH]
    if not starts or not ends:
        raise RuntimeError("the trace holds none of the harness's spans")
    w0, w1 = min(starts), max(ends)
    # Spans that start and end together nest as the program nests them.
    depth = {PROGRAM_SPANS[0]: 1, **dict.fromkeys(PROGRAM_SPANS[1:], 2)}
    spans = sorted(harness + devtrace._host_spans(pd, PROGRAM_SPANS),
                   key=lambda x: (x[0], -x[1], depth.get(x[2], 0)))
    clipped = [(max(s, w0), min(e, w1), n) for s, e, n in spans
               if min(e, w1) > max(s, w0)]
    span_self = devtrace.self_times(clipped)

    planes = sorted((int(m.group(1)), p) for p in pd.planes
                    if (m := devtrace.DEVICE_PLANE.match(p.name)))[:devices]
    scope_ns: dict = collections.defaultdict(float)
    kernels: dict = collections.defaultdict(float)
    op_self: dict = collections.defaultdict(float)
    first_busy = []
    for idx, (_, plane) in enumerate(planes):
        events = _device_events(plane, w0, w1)
        if idx == 0:
            first_busy = devtrace.union((s, e) for s, e, _ in events)
        by_scope = collections.defaultdict(list)
        named = []
        for s, e, name in events:
            scope = scope_of(paths.get(name, ""))
            op = devtrace.op_name(name)
            if scope:
                by_scope[scope].append((s, e))
                op = f"{scope}/{op}"
            if devtrace.KERNEL_MARK in name:
                kernels[kernel_name(name)] += 1 / len(planes)
            named.append((s, e, op))
        for scope, iv in by_scope.items():
            scope_ns[scope] += sum(e - s for s, e in devtrace.union(iv)) \
                / len(planes)
        for op, t in devtrace.self_times(named).items():
            op_self[op] += t / len(planes)
    idle = split_gaps(list(devtrace._gaps(first_busy, w0, w1)),
                      innermost(clipped, w0, w1)) if planes else {}
    return ProgramSummary(batches=len(starts),
                          span_self_ns={n: t for n, t in span_self.items()
                                        if n in PROGRAM_SPANS},
                          scope_ns=dict(scope_ns),
                          kernel_events_by_name=dict(kernels),
                          op_self_ns=dict(op_self), idle_by_span=idle)


def readings(ps: ProgramSummary) -> dict:
    """The per-layer numbers, each per window batch; a number whose
    spans, scope or kernels the trace lacks is left out."""
    out = {}
    if not ps.batches:
        return out
    for metric, span in (("apply_validate_ms", "atomics.apply.validate"),
                         ("apply_to_device_ms", "atomics.apply.to_device"),
                         ("apply_launch_ms", "atomics.apply.launch")):
        if span in ps.span_self_ns:
            out[metric] = ps.span_self_ns[span] / ps.batches / 1e6
    for metric, scope in (("predicate_ms_per_batch", "engine.predicate"),
                          ("commit_ms_per_batch", "engine.commit"),
                          ("sort_ms_per_batch", "engine.sort")):
        if scope in ps.scope_ns:
            out[metric] = ps.scope_ns[scope] / ps.batches / 1e6
    k = ps.kernel_events_by_name
    if "engine_fast_round" in k or "engine_slow_round" in k:
        out["fast_round_share"] = (100.0 * k.get("engine_fast_round", 0.0)
                                   / ps.batches)
    return out


def main(argv=None) -> None:
    from bench import run
    args = run.parse(argv)
    run.setup_paths()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        plan = run.plan_for(json.load(f), args.workload)
    devices = run.start_jax(plan["chips"])
    found = []
    harness_load, harness_reduce = devtrace.load, devtrace.reduce

    def load(log_dir):
        path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        with open(path, "rb") as f:
            found.append(op_paths(f.read()))
        return harness_load(log_dir)

    def both(pd, n):
        found.append(reduce(pd, n, found.pop()))
        return harness_reduce(pd, n)

    # The harness's own traced run, as it is, with the trace read twice.
    devtrace.load, devtrace.reduce = load, both
    try:
        out = run.run_cell(plan, args.seed, args.seconds, True, devices)
    finally:
        devtrace.load, devtrace.reduce = harness_load, harness_reduce
    ps, = found
    out["program"] = {"batches": ps.batches, "readings": readings(ps),
                      "kernel_events": ps.kernel_events_by_name,
                      "breakdown": ps.breakdown(top=16)}
    run.print_result(out)


if __name__ == "__main__":
    sys.exit(main())
