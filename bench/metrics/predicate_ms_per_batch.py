"""Round program, the tier predicate: device time of the scope
`engine.predicate` (`fast_path_ok`: its zero-fill of n + 1 counts, the
scatter of the batch's slots, the max) per batch, mean over the chips."""

from bench.metrics import scope_ms_per_batch


def read(run):
    return scope_ms_per_batch(run, "engine.predicate")
