"""Host entry, one step of it: the program's span `atomics.apply.to_device`
(op and context canonicalization: the batch's host-to-device puts), self
time per batch in the traced window."""

from bench.metrics import span_ms_per_batch


def read(run):
    return span_ms_per_batch(run, "atomics.apply.to_device")
