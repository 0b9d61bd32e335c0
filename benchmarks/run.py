"""Benchmark harness entry point: one bench per paper table/figure plus the
framework's roofline report.

  PYTHONPATH=src python -m benchmarks.run [--quick]
  PYTHONPATH=src python -m benchmarks.run --baseline [--out BENCH_N.json]

--quick shrinks sizes for CI; default finishes in a few minutes on one CPU
core.  Results land in benchmarks/results/*.json.

--baseline runs the FIXED machine-readable perf-regression suite
(benchmarks/baseline.py: atomics fast-path cells, txn MCAS cells, serving
dispatch counts) and writes one JSON document; diff two of them with
`python -m benchmarks.compare OLD NEW` (fails on >10% regression).  The
committed BENCH_<pr>.json at the repo root is the reference every PR is
held to.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import (bench_atomics, bench_cachehash, bench_distributed,
                        bench_faults, bench_llsc, bench_memory, bench_obs,
                        bench_oversub, bench_torn, bench_txn)
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip", default="", help="comma-list to skip")
    ap.add_argument("--baseline", action="store_true",
                    help="run the fixed perf-regression suite and exit")
    ap.add_argument("--out", default="BENCH_baseline.json",
                    help="output path for --baseline")
    args, _ = ap.parse_known_args()
    skip = set(s for s in args.skip.split(",") if s)

    if args.baseline:
        from benchmarks.baseline import run_baseline
        run_baseline(args.out, quick=args.quick)
        return

    benches = [
        ("atomics (Fig 2)", bench_atomics.main),
        ("cachehash (Figs 3-4)", bench_cachehash.main),
        ("torn-state / oversubscription (Fig 2 right)", bench_torn.main),
        ("llsc + sync queue (LL/SC application)", bench_llsc.main),
        ("memory (Table 1)", bench_memory.main),
        ("distributed table (beyond paper)", bench_distributed.main),
        ("txn: MCAS + transactional map (tuples/version-list apps)",
         bench_txn.main),
        ("oversubscribed executor + shard-loss recovery (runtime)",
         bench_oversub.main),
        ("observability: counters sweep + executor trace (repro.obs)",
         bench_obs.main),
        ("fault tolerance: scrub throughput + recovery + shed (repro.guard)",
         bench_faults.main),
    ]
    failures = []
    for name, fn in benches:
        if any(s in name for s in skip):
            print(f"\n##### SKIP {name}")
            continue
        print(f"\n##### {name}")
        t0 = time.time()
        try:
            fn(quick=args.quick)
            print(f"##### done in {time.time()-t0:.1f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()

    # roofline report (needs dryrun_results.json; optional)
    try:
        from benchmarks import bench_roofline
        print("\n##### roofline (from dry-run)")
        bench_roofline.main()
    except SystemExit as e:
        print(e)
    except Exception:
        failures.append("roofline")
        traceback.print_exc()

    if failures:
        print(f"\nFAILED benches: {failures}")
        raise SystemExit(1)
    print("\nAll benchmarks completed.")


if __name__ == "__main__":
    main()
