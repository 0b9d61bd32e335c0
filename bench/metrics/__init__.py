"""Metric readers, one file per metric of BENCHMARK.json, named as the
metric.  Each defines `read(run)`, which returns the metric's value, or
None where the run holds nothing for it to read."""


def span_ms_per_batch(run, span: str):
    """Self time of a host span (`bench.devtrace`) per batch of the traced
    window, in ms; None where the trace holds no such span."""
    if run.trace is None or not run.batches:
        return None
    t = run.trace.span_self_ns.get(span)
    return None if t is None else t / len(run.batches) / 1e6


def scope_ms_per_batch(run, scope: str):
    """Device time of a scope (`bench.devtrace`), averaged over the chips
    used, per batch of the traced window, in ms; None where no op of the
    trace is in the scope."""
    if run.trace is None or not run.batches:
        return None
    t = run.trace.scope_ns.get(scope)
    return None if t is None else t / len(run.batches) / 1e6
