"""Host entry of the sharded table: self time of the program's span
`atomics.dist.apply` and of its steps `.validate` (kind checks, batch
width) and `.launch` (padding, link context, the jit call until it
returns) per batch in the traced window."""

from bench.metrics import span_ms_per_batch

SPANS = ("atomics.dist.apply", "atomics.dist.apply.validate",
         "atomics.dist.apply.launch")


def read(run):
    parts = [t for t in (span_ms_per_batch(run, s) for s in SPANS)
             if t is not None]
    return sum(parts) if parts else None
