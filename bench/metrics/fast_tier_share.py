"""Share of the window's batches for which the program's own fast-tier
predicate (`engine_round.fast_path_ok`) holds, in percent.  Evaluated after
the window on each distinct batch, so the program's current predicate is
what is read."""

import collections

import jax


def read(run):
    spec = getattr(run.cell, "spec", None)
    if spec is None or not hasattr(spec, "n") or not run.batches:
        return None
    from repro.kernels import engine_round
    ok = jax.jit(engine_round.fast_path_ok, static_argnums=0)
    uses = collections.Counter(run.batches)
    fast = sum(n for b, n in uses.items()
               if bool(ok(spec.n, run.cell.ops[b])))
    return 100.0 * fast / len(run.batches)
