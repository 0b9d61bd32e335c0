"""Round program, the slow tier's sort: device time of the scope
`engine.sort` (argsorts of the batch's slots and the sorted gathers) per
batch, mean over the chips.  A batch that takes the fast tier never
sorts."""

from bench.metrics import scope_ms_per_batch


def read(run):
    return scope_ms_per_batch(run, "engine.sort")
