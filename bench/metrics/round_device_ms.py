"""Device-busy time in the traced window (the union of the device's op
intervals, averaged over the chips used) per batch in it: the round
program the system's entry point runs (for a table, `core/engine._apply`)."""


def read(run):
    if run.trace is None or not run.trace.busy_ns or not run.batches:
        return None
    return run.trace.busy_s / len(run.batches) * 1e3
