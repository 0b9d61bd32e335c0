"""repro.obs — observability (DESIGN.md §10).

Counters (`obs.telemetry`): in-graph int32 counters accumulated inside the
existing jitted programs, gated by BIGATOMIC_OBS=off|counters so `off`
compiles to the exact pre-observability programs.  `obs.recorder` keeps
the executor's host counters and the issue latencies its straggler
watchdog reads; `obs.export` writes every counter as JSONL.

Timelines come from the JAX profiler (`jax.profiler.trace`): the entry
points and the executor record host spans and the engine round names its
device scopes there, all on one clock (DESIGN.md §10).
"""

from repro.obs.export import write_metrics_jsonl
from repro.obs.recorder import Recorder
from repro.obs.telemetry import (Telemetry, configured_mode, counters_on,
                                 derived, init_telemetry, record, reset,
                                 snapshot)

__all__ = [
    "Telemetry", "configured_mode", "counters_on",
    "init_telemetry", "record", "reset", "snapshot", "derived",
    "Recorder", "write_metrics_jsonl",
]
