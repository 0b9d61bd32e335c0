"""Ops of all completed batches over the window: from its start to the end
of the last batch (host clock)."""


def read(run):
    return sum(run.ops) / run.window_s
