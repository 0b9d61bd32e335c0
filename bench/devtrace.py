"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

  window      from the first `bench.dispatch` span's start to the last
              `bench.fetch` span's end: the harness's own host spans, on
              the profiler's clock.
  spans       every host event with a dotted name (`<word>.<word>`:
              `bench.fetch`, `atomics.apply.launch`): the harness's and
              the program's `TraceAnnotation`s, not the runtime's own
              events.  A span's self time is its time inside the window
              less that of the spans nested in it.
  device ops  the "XLA Ops" line of each `/device:TPU:<i>` plane.  A trace
              with no TPU plane (a CPU run, in the tests) has its XLA ops
              on host threads instead, each event with an `hlo_op` and a
              `device_ordinal` stat: one device per ordinal.
  busy        the union of a device's op intervals inside the window.
  kernels     ops whose HLO is a `tpu_custom_call` (a Pallas / Mosaic
              kernel), also counted by name without XLA's instance suffix.
  self time   an op's time less that of the ops nested in it (a `cond` or
              `while` holds the ops it runs).
  scopes      an op's scope is the first dotted component of its op-name
              path, as `jax.named_scope` wrote it
              (`jit(_apply_impl)/engine.commit/select_n` -> `engine.commit`).
              A TPU trace keeps the path in the `tf_op` stat of the op's
              event metadata, a CPU trace in the HLO modules of its
              `/host:metadata` plane; `ProfileData` shows neither, so
              `op_paths` reads them from the file's bytes.  A scope's time
              is the union of its ops' intervals.
  idle gaps   each part of a gap in the first device's busy time goes to
              the innermost span the host was in then (`host:<span>`;
              `host:bench.loop` where it was in none).

Per-device numbers are lists, one entry a device used; the dictionaries
hold means over those devices.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

SPAN_DISPATCH = "bench.dispatch"
SPAN_FETCH = "bench.fetch"
HOST_LOOP = "bench.loop"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"
DOTTED = re.compile(r"^[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+$")
SCOPE_STAT = "tf_op"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


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


def load(log_dir: str) -> bytes:
    """The newest `.xplane.pb` under log_dir, as bytes."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no .xplane.pb under {log_dir}")
    with open(max(files, key=os.path.getmtime), "rb") as f:
        return f.read()


@dataclass
class Summary:
    window_ns: float
    busy_ns: list
    kernel_ns: list
    kernel_events: list                # count
    kernel_events_by_name: dict = field(default_factory=dict)
    span_self_ns: dict = field(default_factory=dict)    # span -> ns
    scope_ns: dict = field(default_factory=dict)        # scope -> ns
    op_self_ns: dict = field(default_factory=dict)      # [scope/]op -> ns
    idle_by_span: dict = field(default_factory=dict)    # host:span -> ns

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


def kernel_name(event_name: str) -> str:
    """`%engine_fast_round.1 = (...) custom-call(...)` -> the kernel's
    name without XLA's instance suffix: `engine_fast_round`."""
    return re.sub(r"\.\d+$", "", op_name(event_name))


def scope_of(op_path: str) -> str | None:
    """`jit(_apply_impl)/engine.commit/gather` -> `engine.commit`."""
    return next((part for part in op_path.split("/") if DOTTED.match(part)),
                None)


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
    """events: (start, end, name), nested or disjoint; of two that start
    and end together the first given holds the second.  Returns name ->
    self time."""
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


def _gaps(merged, w0, w1):
    t = w0
    for s, e in merged:
        if s > t:
            yield t, s
        t = max(t, e)
    if w1 > t:
        yield t, w1


# ---------------------------------------------------------------------------
# Op-name paths from the trace file: the XSpace protobuf's wire format
# (xplane.proto of the profiler, hlo.proto of XLA), read for the few
# fields needed.
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
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _sub(buf, span, field_no: int):
    """The values of one field of the message at span."""
    return [v for f, v in _fields(buf, *span) if f == field_no]


def _map_values(buf, plane_fields, field_no: int):
    """The value messages of a map<int64, message> field of a plane."""
    for f, entry in plane_fields:
        if f == field_no:
            for g, value in _fields(buf, *entry):
                if g == 2:
                    yield value


def _hlo_paths(buf, hlo_proto, program_id: int, paths: dict) -> None:
    """HloProto.hlo_module.computations[].instructions[]: each
    instruction's name -> its metadata's op_name."""
    for module in _sub(buf, hlo_proto, 1):
        for comp in _sub(buf, module, 3):
            for inst in _sub(buf, comp, 2):
                f = dict(_fields(buf, *inst))
                meta = dict(_fields(buf, *f[7])) if 7 in f else {}
                if 1 in f and 2 in meta:
                    paths[(program_id, _text(buf, f[1]))] = _text(buf,
                                                                  meta[2])


def op_paths(buf) -> dict:
    """Serialized XSpace -> the op-name path of each device op: under its
    event name, from the `tf_op` stat of a TPU plane's event metadata;
    under (program id, HLO op), from the HLO modules of the metadata
    plane (a CPU trace)."""
    buf = memoryview(buf)
    planes = []
    for f, plane in _fields(buf):
        if f == 1:                                   # XSpace.planes
            fields = list(_fields(buf, *plane))
            planes.append((next((_text(buf, v) for g, v in fields if g == 2),
                                ""), fields))
    # A TPU trace's ops carry their paths; its HLO modules are not read.
    tpu = any(DEVICE_PLANE.match(name) for name, _ in planes)
    paths: dict = {}
    for name, fields in planes:
        if not (DEVICE_PLANE.match(name)
                or (name == METADATA_PLANE and not tpu)):
            continue
        stat_names = {}                              # XPlane.stat_metadata
        for meta in _map_values(buf, fields, 5):
            m = dict(_fields(buf, *meta))
            stat_names[m.get(1, 0)] = _text(buf, m[2]) if 2 in m else ""
        for meta in _map_values(buf, fields, 4):     # XPlane.event_metadata
            m = list(_fields(buf, *meta))
            ev_id = next((v for g, v in m if g == 1), 0)
            ev_name = next((_text(buf, v) for g, v in m if g == 2), None)
            for g, v in m:
                if g != 5:                           # XEventMetadata.stats
                    continue
                stat = dict(_fields(buf, *v))
                kind = stat_names.get(stat.get(1))
                if kind == HLO_STAT and 6 in stat:   # XStat.bytes_value
                    _hlo_paths(buf, stat[6], ev_id, paths)
                elif kind == SCOPE_STAT and ev_name is not None:
                    if 5 in stat:                    # XStat.str_value
                        path = _text(buf, stat[5])
                    else:                            # XStat.ref_value
                        path = stat_names.get(stat.get(7), "")
                    if path:
                        paths[ev_name] = path.rstrip(":")
    return paths


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------

def _host_spans(pd) -> list:
    """(start, end, name) of every dotted-name event on the host planes
    (a name's `#key=value#` metadata cut off), in line order."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if DOTTED.match(name):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  name))
    return spans


def _device_events(pd, devices: int, w0: float, w1: float) -> list:
    """Per device used: its ops as (start, end, event name, path key),
    clipped to the window [w0, w1]."""
    def clip(start, duration, name, key):
        s, e = max(start, w0), min(start + duration, w1)
        return (s, e, name, key) if e > s else None

    tpu = sorted((int(m.group(1)), p) for p in pd.planes
                 if (m := DEVICE_PLANE.match(p.name)))
    if tpu:
        return [[ev for line in plane.lines if line.name == OPS_LINE
                 for ev in (clip(e.start_ns, e.duration_ns, e.name, e.name)
                            for e in line.events) if ev]
                for _, plane in tpu[:devices]]
    by_ordinal = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                if "hlo_op" in st and "device_ordinal" in st:
                    by_ordinal[st["device_ordinal"]].append(clip(
                        ev.start_ns, ev.duration_ns, st["hlo_op"],
                        (st.get("program_id"), st["hlo_op"])))
    return [[ev for ev in by_ordinal[d] if ev]
            for d in sorted(by_ordinal)[:devices]]


def reduce(xspace: bytes, devices: int) -> Summary:
    """Reduce a trace (serialized XSpace) of the harness's window over
    `devices` chips."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    spans = _host_spans(pd)
    starts = [s for s, _, n in spans if n == SPAN_DISPATCH]
    ends = [e for _, e, n in spans if n == SPAN_FETCH]
    if not starts or not ends:
        raise RuntimeError("the trace holds none of the harness's spans")
    w0, w1 = min(starts), max(ends)
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in spans
             if min(e, w1) > max(s, w0)]
    paths = op_paths(xspace)
    per_device = _device_events(pd, devices, w0, w1)
    n_dev = len(per_device)
    busy, kern, kcount, first_busy = [], [], [], []
    kernels: dict = collections.defaultdict(float)
    scope_ns: dict = collections.defaultdict(float)
    op_self: dict = collections.defaultdict(float)
    named_as: dict = {}                # (event name, key) -> (scope, op)
    for idx, events in enumerate(per_device):
        merged = union((s, e) for s, e, _, _ in events)
        if idx == 0:
            first_busy = merged
        busy.append(sum(e - s for s, e in merged))
        ks = [(s, e, n) for s, e, n, _ in events if KERNEL_MARK in n]
        kern.append(sum(e - s for s, e in union((s, e) for s, e, _ in ks)))
        kcount.append(len(ks))
        for _, _, n in ks:
            kernels[kernel_name(n)] += 1 / n_dev
        by_scope = collections.defaultdict(list)
        named = []
        for s, e, n, key in events:
            if (n, key) not in named_as:
                scope = scope_of(paths.get(key, ""))
                named_as[n, key] = (scope, f"{scope}/{op_name(n)}" if scope
                                    else op_name(n))
            scope, op = named_as[n, key]
            if scope:
                by_scope[scope].append((s, e))
            named.append((s, e, op))
        for scope, iv in by_scope.items():
            scope_ns[scope] += sum(e - s for s, e in union(iv)) / n_dev
        for op, t in self_times(named).items():
            op_self[op] += t / n_dev
    idle = (split_gaps(list(_gaps(first_busy, w0, w1)),
                       innermost(spans, w0, w1)) if per_device else {})
    return Summary(window_ns=w1 - w0, busy_ns=busy,
                   kernel_ns=kern, kernel_events=kcount,
                   kernel_events_by_name=dict(kernels),
                   span_self_ns=self_times(spans), scope_ns=dict(scope_ns),
                   op_self_ns=dict(op_self), idle_by_span=idle)
