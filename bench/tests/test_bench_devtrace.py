"""The trace reduction on a small synthetic trace, worked by hand."""

import pytest
from jax.profiler import ProfileData

from bench import devtrace

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


@pytest.fixture(scope="module")
def summary():
    return devtrace.reduce(ProfileData.from_text_proto(TRACE), devices=1)


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
    # gaps [0, 2) (dispatch 1 us, fetch 1 us), [8, 22) (fetch 2 us,
    # loop 10 us, dispatch 1 us, fetch 1 us by their middles) ...
    # Each gap goes whole to the span holding its middle:
    #   [0, 2) middle 1   -> fetch (the fetch span starts at 1)
    #   [8, 22) middle 15 -> loop
    #   [25, 30) middle 27.5 -> fetch
    assert summary.idle_by_span == {"host:bench.fetch": 2_000 + 5_000,
                                    "host:bench.loop": 14_000}
    bd = summary.breakdown()
    assert bd["device_ops"][0] == ["fusion.3", 4e-6]
    assert bd["idle_gaps"][0] == ["host:bench.loop", 14e-6]


def test_two_chips_average():
    s = devtrace.reduce(ProfileData.from_text_proto(TRACE), devices=2)
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
        devtrace.reduce(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'), devices=1)
