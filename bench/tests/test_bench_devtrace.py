"""The trace reduction on small synthetic traces worked by hand, and on a
real CPU trace."""

import types

import pytest
from jax.profiler import ProfileData

from bench import devtrace, run

# Host spans: dispatch [0, 1) us, fetch [1, 10) us, dispatch [20, 21),
# fetch [21, 30).  Device (TPU:0): a cond [2, 8) holding a kernel [3, 5)
# and a fusion [5, 6); a fusion [22, 25); one op [40, 45) after the
# window.  TPU:1 is not used by a one-chip run.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 22000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%cond.7 = (u32[8]) conditional(pred[] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%slow_round_pallas.1 = u32[8] custom-call(u32[8] %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = u32[8] fusion(u32[8] %b)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__apply_impl" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8] fusion()" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 9000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 21000000 duration_ps: 9000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.dispatch" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "other" } }
}
"""


def _xspace(text: str) -> bytes:
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def summary():
    return devtrace.reduce(_xspace(TRACE), devices=1)


def test_window_and_busy(summary):
    assert summary.window_ns == 30_000
    assert summary.busy_ns == [6_000 + 3_000]
    assert summary.busy_s == pytest.approx(9e-6)
    assert summary.window_s == pytest.approx(30e-6)


def test_kernels(summary):
    assert summary.kernel_ns == [2_000]
    assert summary.kernel_events == [1]


def test_self_times(summary):
    assert summary.op_self_ns == {"cond.7": 3_000,
                                  "slow_round_pallas.1": 2_000,
                                  "fusion.3": 4_000}


def test_idle_gaps_by_host_span(summary):
    # Device busy [2, 8) and [22, 25): gaps [0, 2), [8, 22), [25, 30),
    # each part on the span the host was in:
    #   [0, 2):   dispatch [0, 1), fetch [1, 2)
    #   [8, 22):  fetch [8, 10), none [10, 20), dispatch [20, 21),
    #             fetch [21, 22)
    #   [25, 30): fetch
    assert summary.idle_by_span == {"host:bench.dispatch": 2_000,
                                    "host:bench.fetch": 9_000,
                                    "host:bench.loop": 10_000}
    bd = summary.breakdown()
    assert bd["device_ops"][0] == ["fusion.3", 4e-6]
    assert bd["idle_gaps"][0] == ["host:bench.loop", 10e-6]


def test_two_chips_average():
    s = devtrace.reduce(_xspace(TRACE), devices=2)
    assert s.busy_ns == [9_000, 30_000]
    assert s.busy_s == pytest.approx(19.5e-6)


def test_union_and_self_times_by_hand():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3],
                                                                 [5, 9]]
    t = devtrace.self_times([(0, 10, "while"), (1, 2, "a"), (3, 6, "b"),
                             (4, 5, "c"), (12, 13, "a")])
    assert t == {"while": 6, "a": 2, "b": 2, "c": 1}
    assert devtrace.op_name("%fusion.19 = s32[8] fusion(...)") == "fusion.19"


def test_no_spans_is_an_error():
    with pytest.raises(RuntimeError):
        devtrace.reduce(_xspace('planes { id: 1 name: "/host:CPU" }'),
                        devices=1)


# ---------------------------------------------------------------------------
# The program's spans and scopes.  Host (us): batch 1 dispatch [0, 10)
# holding apply [1, 9) with validate [1, 2), to_device [2, 5), launch
# [5, 8); fetch [10, 20).  Batch 2 dispatch [30, 34) holding apply [30, 34)
# with validate [30, 31), to_device [31, 32), launch [32, 33); fetch
# [34, 40).  Window [0, 40).  TPU:0: the predicate [6, 7); a cond [7, 14)
# holding the fast kernel [8, 11) and a results fusion [11, 12); the
# commit [14, 15); an unscoped copy [15, 16); batch 2's predicate [33, 35)
# and kernel [35, 36).  TPU:1: the predicate [6, 8), the kernel [9, 11),
# the commit [14, 16); batch 2's predicate [33, 34) and kernel [35, 36).
# ---------------------------------------------------------------------------

_TPU0 = [(1, 6, 1), (2, 7, 7), (3, 8, 3), (4, 11, 1), (5, 14, 1), (6, 15, 1),
         (1, 33, 2), (3, 35, 1)]
_TPU1 = [(1, 6, 2), (3, 9, 2), (5, 14, 2), (1, 33, 1), (3, 35, 1)]
# Spans that start and end together are given outer first.
_SPANS = [(1, 0, 10), (2, 1, 8), (3, 1, 1), (4, 2, 3), (5, 5, 3),
          (6, 10, 10), (1, 30, 4), (2, 30, 4), (3, 30, 1), (4, 31, 1),
          (5, 32, 1), (6, 34, 6)]
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


def _q(text: str) -> str:
    return text.replace('"', '\\"')


def _events(events) -> str:
    return " ".join(f"events {{ metadata_id: {m} offset_ps: {s * 10**6} "
                    f"duration_ps: {d * 10**6} }}" for m, s, d in events)


def _device_plane(index: int, events) -> str:
    """A TPU plane whose ops carry their op-name path as the trace file
    does: a `tf_op` stat of the event metadata (a string, or for the first
    op a reference to a stat metadata name, with XLA's trailing colon),
    beside a stat of another kind."""
    metas = []
    for i, (name, path) in enumerate(_OPS, 1):
        stats = 'stats { metadata_id: 2 str_value: "loop fusion" }'
        if i == 1:
            stats += " stats { metadata_id: 1 ref_value: 9 }"
        elif path:
            stats += f' stats {{ metadata_id: 1 str_value: "{path}:" }}'
        metas.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{_q(name)}" {stats} }} }}')
    return f"""
planes {{
  id: {index + 1} name: "/device:TPU:{index}"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_events(events)} }}
  {" ".join(metas)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "hlo_category" }} }}
  stat_metadata {{ key: 9 value {{ id: 9 name: "{_OPS[0][1]}:" }} }}
}}"""


def _program_trace() -> str:
    """Both TPU planes, and a host plane whose metadata is not a device
    op's, though it carries a `tf_op` stat."""
    spans = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        + ('stats { metadata_id: 1 str_value: "jit(x)/engine.sort/sort" } '
           if i == 1 else "") + "} }"
        for i, n in enumerate(_NAMES, 1))
    return _device_plane(0, _TPU0) + _device_plane(1, _TPU1) + f"""
planes {{
  id: 9 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {_events(_SPANS)} }}
  {spans}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}"""


PROGRAM = _program_trace()


@pytest.fixture(scope="module")
def program():
    return devtrace.reduce(_xspace(PROGRAM), devices=1)


def test_op_paths_from_the_trace_file():
    assert devtrace.op_paths(_xspace(PROGRAM)) == {
        name: path for name, path in _OPS if path}


def test_span_self_times(program):
    # apply: 8 - (1 + 3 + 3) in batch 1, 4 - 3 in batch 2; dispatch:
    # 10 - 8 and 4 - 4.
    assert program.span_self_ns == {"bench.dispatch": 2_000,
                                    "bench.fetch": 16_000,
                                    "atomics.apply": 2_000,
                                    "atomics.apply.validate": 2_000,
                                    "atomics.apply.to_device": 4_000,
                                    "atomics.apply.launch": 4_000}


def test_scopes_and_kernels(program):
    assert program.scope_ns == {"engine.predicate": 1_000 + 2_000,
                                "engine.fast_round": 3_000 + 1_000,
                                "engine.results": 1_000,
                                "engine.commit": 1_000}
    assert program.kernel_events_by_name == {"engine_fast_round": 2}


def test_breakdown_names_ops_by_scope(program):
    assert program.op_self_ns == {
        "engine.predicate/fusion.19": 3_000,
        "cond.3": 7_000 - 3_000 - 1_000,
        "engine.fast_round/engine_fast_round.1": 4_000,
        "engine.results/fusion.5": 1_000,
        "engine.commit/negate_select_fusion.6": 1_000,
        "copy.259": 1_000}
    assert program.breakdown(top=1)["device_ops"] == [
        ["engine.fast_round/engine_fast_round.1", 4e-6]]


def test_idle_gaps_split_over_innermost_spans(program):
    # Device busy [6, 16) and [33, 36): gaps [0, 6), [16, 33), [36, 40).
    #   [0, 6):   dispatch [0, 1), validate 1, to_device 3, launch [5, 6)
    #   [16, 33): fetch [16, 20), none [20, 30), validate, to_device,
    #             launch 1 each
    #   [36, 40): fetch
    assert program.idle_by_span == {"host:bench.dispatch": 1_000,
                                    "host:atomics.apply.validate": 2_000,
                                    "host:atomics.apply.to_device": 4_000,
                                    "host:atomics.apply.launch": 2_000,
                                    "host:bench.fetch": 4_000 + 4_000,
                                    "host:bench.loop": 10_000}
    assert sum(program.idle_by_span.values()) == 6_000 + 17_000 + 4_000


READERS = ("apply_to_device_ms", "apply_launch_ms", "predicate_ms_per_batch",
           "commit_ms_per_batch", "sort_ms_per_batch")


def _read(summary, names=READERS, batches=2) -> dict:
    fake = types.SimpleNamespace(trace=summary, batches=list(range(batches)))
    return {n: run.reader(n)(fake) for n in names}


def test_readings(program):
    """Per batch, in ms; the trace has no `engine.sort`, so its reader
    finds nothing."""
    assert _read(program) == pytest.approx({
        "apply_to_device_ms": 2e-3, "apply_launch_ms": 2e-3,
        "predicate_ms_per_batch": 1.5e-3, "commit_ms_per_batch": 0.5e-3,
        "sort_ms_per_batch": None})


def test_harness_reduction_is_untouched(program):
    """The program's spans and scopes read beside the harness's window,
    busy time and kernels, which they leave as they were."""
    assert program.window_ns == 40_000
    assert program.busy_ns == [10_000 + 3_000]
    assert program.kernel_ns == [4_000]
    assert program.kernel_events == [2]


def test_fields_read_as_before_the_merge():
    """Two chips, nested spans, scopes on both planes, a kernel and idle
    gaps: the fields the existing readers use are what the reduction gave
    before it read the program's spans and scopes (window_ns 40_000,
    busy_ns [13_000, 8_000], kernel_ns [4_000, 3_000], kernel_events
    [2, 2]), and the existing readers' values with them.  Scopes are
    averaged over the chips."""
    s = devtrace.reduce(_xspace(PROGRAM), devices=2)
    assert s.window_ns == 40_000
    assert s.busy_ns == [13_000, 8_000]
    assert s.kernel_ns == [4_000, 3_000]
    assert s.kernel_events == [2, 2]
    assert _read(s, ("round_device_ms", "kernel_ms_per_batch",
                     "device_idle_share")) == pytest.approx({
        "round_device_ms": 10.5e-3 / 2, "kernel_ms_per_batch": 3.5e-3 / 2,
        "device_idle_share": 100 * (1 - 10.5 / 40)})
    assert s.scope_ns == {"engine.predicate": 3_000,
                          "engine.fast_round": 3_500,
                          "engine.results": 500, "engine.commit": 1_500}
    assert s.kernel_events_by_name == {"engine_fast_round": 2}
    # The idle split follows the first chip.
    assert s.idle_by_span == devtrace.reduce(_xspace(PROGRAM),
                                             devices=1).idle_by_span


def test_a_trace_without_the_program_reads_nothing(summary):
    """On a trace of a program without spans or scopes every reading is
    left out, as a reader returns None."""
    assert set(summary.span_self_ns) == {"bench.dispatch", "bench.fetch"}
    assert summary.scope_ns == {}
    assert summary.kernel_events_by_name == {"slow_round_pallas": 1}
    assert set(_read(summary).values()) == {None}


def test_innermost_pieces_by_hand():
    spans = [(0, 10, "outer"), (2, 4, "a"), (4, 8, "b"), (5, 6, "c"),
             (12, 14, "d")]
    assert devtrace.innermost(spans, 1, 13) == [
        (1, 2, "outer"), (2, 4, "a"), (4, 5, "b"), (5, 6, "c"),
        (6, 8, "b"), (8, 10, "outer"), (10, 12, None), (12, 13, "d")]
    assert devtrace.split_gaps([(3, 5), (9, 13)],
                               devtrace.innermost(spans, 1, 13)) == {
        "host:a": 1, "host:b": 1, "host:outer": 1, "host:bench.loop": 2,
        "host:d": 1}
    assert devtrace.scope_of("jit(f)/cond/engine.sort/sort") == "engine.sort"
    assert devtrace.scope_of("jit(f)/engine_fast_round/x") is None
    assert devtrace.scope_of("jit(f)/shmap/dist.route/all-to-all") \
        == "dist.route"


def test_a_cpu_trace_reads_ops_and_scopes(tmp_path):
    """Without a TPU the XLA ops on the host's threads stand in for a
    device, and their scopes come from the trace's HLO modules."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("probe.sort"):
            x = jnp.sort(x)
        with jax.named_scope("probe.scale"):
            return x * 3 + 1

    x = jnp.arange(1 << 14, dtype=jnp.float32)[::-1]
    f(x).block_until_ready()
    devtrace.start(str(tmp_path))
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation(devtrace.SPAN_DISPATCH):
                y = f(x)
            with jax.profiler.TraceAnnotation(devtrace.SPAN_FETCH):
                y.block_until_ready()
    finally:
        devtrace.stop()
    s = devtrace.reduce(devtrace.load(str(tmp_path)), devices=1)
    assert s.span_self_ns["bench.dispatch"] > 0 and len(s.busy_ns) == 1
    assert 0 < s.busy_ns[0] <= s.window_ns
    assert set(s.scope_ns) == {"probe.sort", "probe.scale"}
    assert all(t > 0 for t in s.scope_ns.values())
    assert any(op.startswith("probe.sort/") for op in s.op_self_ns)
    assert s.kernel_ns == [0] and s.kernel_events_by_name == {}
