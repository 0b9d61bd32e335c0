"""Host entry, one step of it: the program's span `atomics.apply.launch`
(mode, telemetry carry, the jit call until it returns), self time per
batch in the traced window."""

from bench.metrics import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "atomics.apply.launch")
