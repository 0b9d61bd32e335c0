"""repro.obs smoke bench: drive the counters and the profiler timeline and
export both artifact kinds.

  * counters sweep — a fixed mixed LOAD/STORE/CAS + MCAS + queue workload
    under BIGATOMIC_OBS=counters; the full snapshot (+ derived rates, +
    the executor's host counters) lands in
    benchmarks/results/obs_metrics.jsonl.
  * trace run — an oversubscribed executor with an injected straggler
    delay under `jax.profiler.trace`: its `executor.*` spans, the
    `atomics.apply` spans inside them and the round program's device ops
    land in benchmarks/results/obs_trace/ (TensorBoard's profile plugin
    or Perfetto reads it).

CI's `obs` job runs this with --quick and uploads both as workflow
artifacts.
"""

from __future__ import annotations

import contextlib
import os

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")
TRACE_DIR = os.path.join(RESULTS, "obs_trace")


@contextlib.contextmanager
def _obs_mode(mode: str):
    saved = os.environ.get("BIGATOMIC_OBS")
    os.environ["BIGATOMIC_OBS"] = mode
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("BIGATOMIC_OBS", None)
        else:
            os.environ["BIGATOMIC_OBS"] = saved


def counters_sweep(quick: bool = False) -> dict:
    """The fixed counter workload; returns the snapshot it produced.
    Assumes BIGATOMIC_OBS=counters is already in force."""
    import numpy as np

    from repro import atomics, obs
    from repro.core import engine

    obs.reset()
    n, k, p = 256, 2, 64
    batches = 4 if quick else 16
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    state, ctx = engine.init(spec), None
    rng = np.random.default_rng(0)
    for b in range(batches):
        kind = rng.integers(0, 3, p).astype(np.int32)   # LOAD/STORE/CAS
        if b % 2:
            # contended: half the lanes hammer 4 hot cells (slow path) ...
            slot = np.where(rng.random(p) < 0.5,
                            rng.integers(0, 4, p),
                            rng.integers(0, n, p)).astype(np.int32)
        else:
            # ... alternating with all-distinct batches (fast path).
            slot = rng.permutation(n)[:p].astype(np.int32)
        current = np.asarray(atomics.logical(spec, state))
        expected = np.where((rng.random(p) < 0.5)[:, None],
                            current[slot],
                            rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32))
        desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
        ops = atomics.make_ops(kind, slot, expected.astype(np.uint32),
                               desired, k=k)
        state, ctx, _, _, _ = engine.apply(spec, state, ops, ctx)

    # one MCAS round (mcas.* counters) ...
    t, w = 16, 3
    slots = np.stack([rng.choice(n, w, replace=False)
                      for _ in range(t)]).astype(np.int32)
    current = np.asarray(atomics.logical(spec, state))
    expected = np.where((rng.random(t) < 0.6)[:, None, None],
                        current[slots],
                        rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32))
    txns = atomics.make_txns(slots, expected.astype(np.uint32),
                             rng.integers(0, 2 ** 32, (t, w, k),
                                          dtype=np.uint32), k=k)
    atomics.mcas(spec, state, txns)

    # ... and one over-subscribed queue run (queue.* host counters).
    from repro.sync.queue import BigQueue
    q = BigQueue(8, k=2, strategy="cached_me")
    q.enqueue_batch(np.arange(12, dtype=np.uint32))
    q.dequeue_batch(12)
    return obs.snapshot()


def trace_run(quick: bool = False):
    """One oversubscribed executor run with a straggler fault, under the
    JAX profiler (trace in TRACE_DIR); returns the Recorder."""
    import jax

    from repro import atomics
    from repro.obs import Recorder
    from repro.runtime import (Executor, Fault, FaultInjector, LocalTarget,
                               SyntheticStream)

    n, k, width = 128, 2, 16
    n_batches = 4 if quick else 12
    target = LocalTarget(atomics.AtomicSpec(n, k, "seqlock", p_max=64))
    streams = [SyntheticStream(f"s{i}", seed=i, n=n, k=k, width=width,
                               n_batches=n_batches, hot_cells=4,
                               hot_frac=0.25)
               for i in range(4)]
    rcd = Recorder()
    ex = Executor(target, streams, slots=2, oversubscription=2,
                  injector=FaultInjector([Fault(round=2, kind="delay",
                                                stream=1, seconds=0.01,
                                                rounds=3)]),
                  recorder=rcd)
    with jax.profiler.trace(TRACE_DIR):
        ex.run()
    return rcd


def main(quick: bool = False) -> None:
    from repro import obs

    os.makedirs(RESULTS, exist_ok=True)
    with _obs_mode("counters"):
        snap = counters_sweep(quick)
        rcd = trace_run(quick)
        metrics_path = os.path.join(RESULTS, "obs_metrics.jsonl")
        obs.write_metrics_jsonl(metrics_path, extra=rcd.metrics())

    rates = obs.derived(snap)
    print(f"  engine batches      {snap['engine.batches']}")
    print(f"  fast-path hit rate  {rates['hit_rate_fast']:.2f}")
    print(f"  mean slow rounds    {rates['mean_slow_rounds']:.2f}")
    print(f"  mcas commits/aborts {snap['mcas.commits']}/{snap['mcas.aborts']}")
    print(f"  queue rounds        {snap.get('queue.rounds', 0)}")
    print(f"  executor issues     {rcd.metrics()['exec.issues']}")
    print(f"  wrote {metrics_path}")
    print(f"  wrote {TRACE_DIR}")


if __name__ == "__main__":
    import sys
    main(quick="--quick" in sys.argv)
