"""Exchange of the sharded table: device time of the sharded round's
scopes `dist.route` (owner and rank, packing, the outbound all_to_alls)
and `dist.return` (the inbound all_to_alls, the merge into the issuing
lanes) per batch, mean over the chips.  The return waits on the slowest
shard, so a hot shard shows here."""

from bench.metrics import scope_ms_per_batch

SCOPES = ("dist.route", "dist.return")


def read(run):
    parts = [t for t in (scope_ms_per_batch(run, s) for s in SCOPES)
             if t is not None]
    return sum(parts) if parts else None
