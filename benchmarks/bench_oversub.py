"""Oversubscribed multi-stream execution (repro.runtime, DESIGN.md §9).

The paper's throughput regime keeps MORE logical streams in flight than
compute slots; the executor's async-dispatch window must make that (nearly)
free.  Sweep: oversubscription factor × contention against one local
big-atomic table, at constant TOTAL work — the acceptance cell (ISSUE 7)
is factor >= 4 throughput within 2x of the 1-stream-per-slot baseline.

A subprocess cell (8 placeholder devices) additionally injects a mid-round
shard loss into a distributed executor run and reports the measured
recovery latency (checkpoint restore + reshard onto survivors + journal
replay) — the number committed in BENCH_7.json.
"""

from __future__ import annotations

import sys
import textwrap
import time

from benchmarks.common import print_table, run_cpu_child, save_results

# Fixed baseline shapes (independent of --quick, see baseline.py).
N, K, WIDTH, SLOTS = 1 << 10, 4, 256, 2
TOTAL_BATCHES = 48


def run_oversub_cell(strategy: str, *, factor: int, hot_frac: float,
                     reps: int = 3) -> dict:
    """One sweep cell: S = SLOTS*factor streams, in-flight budget
    SLOTS*factor, TOTAL_BATCHES batches of WIDTH lanes split evenly."""
    import numpy as np

    from repro import atomics
    from repro.runtime import Executor, LocalTarget, SyntheticStream

    n_streams = SLOTS * factor
    per_stream = TOTAL_BATCHES // n_streams
    spec = atomics.AtomicSpec(N, K, strategy, p_max=WIDTH)
    rng = np.random.default_rng(0)
    init = rng.integers(0, 2 ** 32, (N, K), dtype=np.uint32)

    def once() -> float:
        target = LocalTarget(spec, init)
        streams = [SyntheticStream(f"s{i}", seed=i, n=N, k=K, width=WIDTH,
                                   n_batches=per_stream, hot_cells=4,
                                   hot_frac=hot_frac)
                   for i in range(n_streams)]
        ex = Executor(target, streams, slots=SLOTS, oversubscription=factor)
        t0 = time.perf_counter()
        ex.run()
        return time.perf_counter() - t0

    once()                                        # compile warmup
    dt = min(once() for _ in range(reps))
    lanes = TOTAL_BATCHES * WIDTH
    return dict(strategy=strategy, factor=factor, streams=n_streams,
                contention=("hot" if hot_frac else "uniform"),
                batches=TOTAL_BATCHES,
                mops_s=round(lanes / dt / 1e6, 3))


RECOVERY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, numpy as np
    from repro import atomics
    from repro.core import distributed as dsb
    from repro.launch.mesh import make_mesh
    from repro.runtime import (DistTarget, Executor, Fault, FaultInjector,
                               SyntheticStream)

    n, k, strategy = 32, 2, "seqlock"

    def factory(n_surviving):
        s = 1
        while s * 2 <= n_surviving and n % (s * 2) == 0:
            s *= 2
        mesh = make_mesh((s, 8 // s), ("shard", "rest"))
        return mesh, dsb.DistSpec(
            atomics.AtomicSpec(n, k, strategy, p_max=64), "shard", s,
            32 // s)

    rng = np.random.default_rng(0)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    mesh0, dspec0 = factory(8)
    target = DistTarget(mesh0, dspec0, init, mesh_factory=factory)
    streams = [SyntheticStream(f"s{i}", seed=i, n=n, k=k,
                               width=dspec0.p_global, n_batches=3)
               for i in range(4)]
    inj = FaultInjector([Fault(round=2, kind="shard_loss", shard=3,
                               after_issues=1)])
    ex = Executor(target, streams, slots=1, oversubscription=4,
                  injector=inj, checkpoint_every=2)
    rep = ex.run()
    (rec,) = rep["recoveries"]
    print("JSON:" + json.dumps(dict(
        latency_s=rec["latency_s"], replayed=rec["replayed"],
        shards_after=rec["n_shards"], issues=rep["issues"])))
""")


def run_recovery_cell() -> dict:
    """Injected mid-round shard loss on the 8-device fixture: measured
    recovery latency (restore + reshard + replay + re-checkpoint)."""
    return dict(run_cpu_child(RECOVERY_SCRIPT, timeout=900), backend="cpu")


def main(quick: bool = False):
    reps = 2 if quick else 3
    strategies = ("seqlock",) if quick else ("seqlock", "cached_wf")
    rows = []
    for strategy in strategies:
        base = {}
        for hot_frac in (0.0, 0.5):
            for factor in (1, 2, 4) if quick else (1, 2, 4, 8):
                cell = run_oversub_cell(strategy, factor=factor,
                                        hot_frac=hot_frac, reps=reps)
                if factor == 1:
                    base[hot_frac] = cell["mops_s"]
                cell["x_of_f1"] = round(cell["mops_s"] / base[hot_frac], 3)
                rows.append(cell)
    print_table("Oversubscribed executor (S = 2*factor streams, 2 slots)",
                rows, ["strategy", "factor", "streams", "contention",
                       "mops_s", "x_of_f1"])
    for r in rows:
        if r["factor"] == 4:
            assert r["x_of_f1"] >= 0.5, \
                f"factor-4 throughput fell below 2x of baseline: {r}"
    print("acceptance: factor-4 cells within 2x of 1-stream-per-slot "
          "baseline: OK")

    rec = run_recovery_cell()
    print(f"\nshard-loss recovery (8 -> {rec['shards_after']} shards, "
          f"{rec['replayed']} batches replayed): {rec['latency_s']:.2f}s")
    save_results("bench_oversub", dict(sweep=rows, recovery=rec))
    return rows, rec


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
