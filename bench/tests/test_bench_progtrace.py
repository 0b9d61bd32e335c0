"""The program's spans and scopes in a small synthetic trace, worked by
hand."""

import pytest
from jax.profiler import ProfileData

from bench import devtrace, progtrace
from bench.tests.test_bench_devtrace import TRACE as HARNESS_ONLY

# Host (us): batch 1 dispatch [0, 10) holding apply [1, 9) with validate
# [1, 2), to_device [2, 5), launch [5, 8); fetch [10, 20).  Batch 2
# dispatch [30, 34) holding apply [30, 34) with validate [30, 31),
# to_device [31, 32), launch [32, 33); fetch [34, 40).  Window [0, 40).
# Device (TPU:0): the predicate [6, 7); a cond [7, 14) holding the fast
# kernel [8, 11) and a results fusion [11, 12); the commit [14, 15); an
# unscoped copy [15, 16); batch 2's predicate [33, 35) and kernel [35, 36).
_DEVICE = [
    (1, 6, 1), (2, 7, 7), (3, 8, 3), (4, 11, 1), (5, 14, 1), (6, 15, 1),
    (1, 33, 2), (3, 35, 1),
]
_SPANS = [
    (1, 0, 10), (2, 1, 8), (3, 1, 1), (4, 2, 3), (5, 5, 3), (6, 10, 10),
    (1, 30, 4), (2, 30, 4), (3, 30, 1), (4, 31, 1), (5, 32, 1),
    (6, 34, 6),
]
_APPLY = "jit(_apply_impl)"
_OPS = [
    ("%fusion.19 = s32[9] fusion(s32[8] %a)",
     f"{_APPLY}/engine.predicate/scatter-add"),
    ("%cond.3 = (u32[8]) conditional(pred[] %p)", f"{_APPLY}/cond"),
    ('%engine_fast_round.1 = u32[8] custom-call(u32[8] %b), '
     'custom_call_target="tpu_custom_call"',
     f"{_APPLY}/cond/branch_1_fun/engine.fast_round/jit(fast_round_pallas)"
     "/engine_fast_round/pallas_call"),
    ("%fusion.5 = pred[8] fusion(u32[8] %c)",
     f"{_APPLY}/cond/branch_1_fun/engine.results/select_n"),
    ("%negate_select_fusion.6 = s32[16] fusion(s32[16] %d)",
     f"{_APPLY}/engine.commit/select_n"),
    ("%copy.259 = u32[16] copy(u32[16] %e)", None),
]
_NAMES = ["bench.dispatch", "atomics.apply", "atomics.apply.validate",
          "atomics.apply.to_device", "atomics.apply.launch", "bench.fetch"]


def _event(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _trace() -> str:
    def meta(names):
        return " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for i, n in enumerate(names, 1))
    ops_meta = meta(name.replace('"', '\\"') for name, _ in _OPS)
    spans_meta = meta(_NAMES)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {" ".join(_event(*e) for e in _DEVICE)}
  }}
  {ops_meta}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {" ".join(_event(*e) for e in _SPANS)}
  }}
  {spans_meta}
}}
"""


def _pb(*fields) -> bytes:
    """A protobuf message from (field number, int | str | bytes) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += varint(no << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(no << 3 | 2) + varint(len(v)) + v
    return out


def _xspace() -> bytes:
    """The same device ops' metadata as the trace file holds it: the
    op-name path in a `tf_op` stat (a string, or a reference to a stat
    metadata name), beside stats of other kinds; a host plane's metadata
    is not the device's."""
    stat_meta = [(5, _pb((1, k), (2, _pb((1, k), (2, n)))))
                 for k, n in ((1, "tf_op"), (2, "hlo_category"),
                              (9, _OPS[0][1] + ":"))]
    metas = []
    for i, (name, path) in enumerate(_OPS, 1):
        stats = [(5, _pb((1, 2), (5, "loop fusion")))]
        if i == 1:
            stats.append((5, _pb((1, 1), (7, 9))))
        elif path:
            stats.append((5, _pb((1, 1), (5, path + ":"))))
        metas.append((4, _pb((1, i), (2, _pb((1, i), (2, name), *stats)))))
    device = _pb((1, 1), (2, "/device:TPU:0"), *metas, *stat_meta)
    host = _pb((1, 2), (2, "/host:CPU"), (4, _pb((1, 1), (2, _pb(
        (1, 1), (2, "bench.dispatch"),
        (5, _pb((1, 1), (5, "jit(x)/engine.sort/sort"))))))), *stat_meta)
    return _pb((1, host), (1, device))


@pytest.fixture(scope="module")
def summary():
    return progtrace.reduce(ProfileData.from_text_proto(_trace()), devices=1,
                            paths=progtrace.op_paths(_xspace()))


def test_op_paths_from_the_trace_file():
    assert progtrace.op_paths(_xspace()) == {
        name: path for name, path in _OPS if path}


def test_span_self_times(summary):
    # apply: 8 - (1 + 3 + 3) in batch 1, 4 - 3 in batch 2.
    assert summary.batches == 2
    assert summary.span_self_ns == {"atomics.apply": 2_000,
                                    "atomics.apply.validate": 2_000,
                                    "atomics.apply.to_device": 4_000,
                                    "atomics.apply.launch": 4_000}


def test_scopes_and_kernels(summary):
    assert summary.scope_ns == {"engine.predicate": 1_000 + 2_000,
                                "engine.fast_round": 3_000 + 1_000,
                                "engine.results": 1_000,
                                "engine.commit": 1_000}
    assert summary.kernel_events_by_name == {"engine_fast_round": 2}


def test_breakdown_names_ops_by_scope(summary):
    assert summary.op_self_ns == {
        "engine.predicate/fusion.19": 3_000,
        "cond.3": 7_000 - 3_000 - 1_000,
        "engine.fast_round/engine_fast_round.1": 4_000,
        "engine.results/fusion.5": 1_000,
        "engine.commit/negate_select_fusion.6": 1_000,
        "copy.259": 1_000}
    assert summary.breakdown(top=1)["device_ops"] == [
        ["engine.fast_round/engine_fast_round.1", 4e-6]]


def test_idle_gaps_split_over_innermost_spans(summary):
    # Device busy [6, 16) and [33, 36): gaps [0, 6), [16, 33), [36, 40).
    #   [0, 6):   dispatch [0, 1), validate 1, to_device 3, launch [5, 6)
    #   [16, 33): fetch [16, 20), none [20, 30), validate, to_device,
    #             launch 1 each
    #   [36, 40): fetch
    assert summary.idle_by_span == {"host:bench.dispatch": 1_000,
                                    "host:atomics.apply.validate": 2_000,
                                    "host:atomics.apply.to_device": 4_000,
                                    "host:atomics.apply.launch": 2_000,
                                    "host:bench.fetch": 4_000 + 4_000,
                                    "host:bench.loop": 10_000}
    assert sum(summary.idle_by_span.values()) == 6_000 + 17_000 + 4_000


def test_readings(summary):
    r = progtrace.readings(summary)
    assert r == pytest.approx({"apply_validate_ms": 1e-3,
                               "apply_to_device_ms": 2e-3,
                               "apply_launch_ms": 2e-3,
                               "predicate_ms_per_batch": 1.5e-3,
                               "commit_ms_per_batch": 0.5e-3,
                               "fast_round_share": 100.0})


def test_harness_reduction_is_untouched():
    """The program's reduction reads beside the harness's, which sees the
    same window, busy time and kernels in the same trace."""
    pd = ProfileData.from_text_proto(_trace())
    harness = devtrace.reduce(pd, devices=1)
    assert harness.window_ns == 40_000
    assert harness.busy_ns == [10_000 + 3_000]
    assert harness.kernel_ns == [4_000]
    assert harness.kernel_events == [2]


def test_a_trace_without_the_program_reads_nothing():
    """On a trace of a program without spans or scopes (the harness's own
    fixture) every reading is left out, as a reader would return None."""
    s = progtrace.reduce(ProfileData.from_text_proto(HARNESS_ONLY),
                         devices=1, paths={})
    assert s.span_self_ns == {} and s.scope_ns == {}
    assert s.kernel_events_by_name == {"slow_round_pallas": 1}
    assert progtrace.readings(s) == {}


def test_innermost_pieces_by_hand():
    spans = [(0, 10, "outer"), (2, 4, "a"), (4, 8, "b"), (5, 6, "c"),
             (12, 14, "d")]
    assert progtrace.innermost(spans, 1, 13) == [
        (1, 2, "outer"), (2, 4, "a"), (4, 5, "b"), (5, 6, "c"),
        (6, 8, "b"), (8, 10, "outer"), (10, 12, None), (12, 13, "d")]
    assert progtrace.split_gaps([(3, 5), (9, 13)],
                                progtrace.innermost(spans, 1, 13)) == {
        "host:a": 1, "host:b": 1, "host:outer": 1, "host:bench.loop": 2,
        "host:d": 1}
    assert progtrace.scope_of("jit(f)/cond/engine.sort/sort") == "engine.sort"
    assert progtrace.scope_of("jit(f)/engine_fast_round/x") is None
