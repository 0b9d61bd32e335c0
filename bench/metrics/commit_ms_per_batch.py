"""Round program, the commit: device time of the scope `engine.commit`
(the strategy's `commit`; for cached_me, passes over all n records) per
batch, mean over the chips."""

from bench.metrics import scope_ms_per_batch


def read(run):
    return scope_ms_per_batch(run, "engine.commit")
