"""repro.txn workload family: txn width × contention × strategy ×
abort-backoff, single-device and mesh-sharded (ISSUE 4 satellite).

Three sweeps, all emitted to benchmarks/results/bench_txn.json:

  mcas        batched k-word MCAS on one device.  Width W is the number of
              cells per transaction; contention is the table size (small n
              forces overlapping claim sets, so arbitration serializes
              rounds); the backoff axis compares Dice-style abort policies
              (none / const / capped-exp) on commit throughput and wasted
              rounds.  commit_rate counts txns whose comparands survived
              to commit; attempts/txn is the arbitration-loss metric.

  map         optimistic transactional map: T read-modify-write txns on a
              CacheHash, from disjoint keys (all commit round 1) to one
              hot counter key (fully serialized, T rounds) — the OCC
              conflict spectrum.

  mcas_dist   cross-shard MCAS through the two-round prepare/commit
              collective, shard counts {1→8} on 8 placeholder devices
              (subprocess), with the exact per-device collective-word
              model (`distributed.mcas_collective_words`).

Usage:
  PYTHONPATH=src python -m benchmarks.bench_txn [--quick] [--tiny]

--tiny is the CI smoke mode (a few seconds): one strategy, one size,
single device only.
"""

from __future__ import annotations

import textwrap

import numpy as np

from benchmarks.common import print_table, run_cpu_child, save_results, time_op
from repro import atomics
from repro.sync.queue import BackoffPolicy

STRATEGIES = ["seqlock", "indirect", "cached_wf", "cached_me"]
POLICIES = [BackoffPolicy("none"), BackoffPolicy("const", 1),
            BackoffPolicy("exp", 1, 4)]


def _txns(rng, *, t, w, n, k):
    slot = np.stack([rng.choice(n, size=w, replace=False)
                     for _ in range(t)]).astype(np.int32)
    expected = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    desired = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    return slot, expected, desired


def run_mcas_cell(strategy, policy, *, t, w, n, k, match_frac, reps=3,
                  seed=0):
    rng = np.random.default_rng(seed)
    spec = atomics.AtomicSpec(n, k, strategy, p_max=max(t * w, 64))
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    state = atomics.init(spec, init)
    slot, expected, desired = _txns(rng, t=t, w=w, n=n, k=k)
    fresh = rng.random(t) < match_frac
    expected[fresh] = init[slot[fresh]]
    txns = atomics.make_txns(slot, expected, desired, k=k)

    def step(state, txns):
        return atomics.mcas(spec, state, txns, policy=policy)

    dt, (st2, res) = time_op(step, state, txns, reps=reps)
    succ = np.asarray(res.success)
    return {
        "strategy": strategy, "policy": policy.kind, "t": t, "w": w, "n": n,
        "ktxn_s": round(t / dt / 1e3, 2),
        "commit_rate": float(succ.mean()),
        "rounds": int(res.rounds),
        "attempts_txn": float(np.asarray(res.attempts).mean()),
    }


def _fn_rmw(rv, rf):
    return rv.sum(axis=1, keepdims=True) + 1


def run_map_cell(strategy, *, t, hot: bool, seed=0):
    from repro.core import cachehash as ch
    from repro.txn import map as txn_map
    rng = np.random.default_rng(seed)
    hs = atomics.HashSpec(256, vw=1, strategy=strategy, p_max=max(4 * t, 64))
    state = ch.init_hash(hs)
    keys = (np.full((t, 1), 7, np.uint32) if hot
            else rng.choice(200, size=t, replace=False)
            .astype(np.uint32)[:, None])
    txns = txn_map.make_map_txns(keys, keys)

    def step(state, txns):
        return txn_map.transact(hs, state, txns, _fn_rmw)

    dt, (st2, res) = time_op(step, state, txns, reps=3)
    return {
        "strategy": strategy, "workload": "hot-key" if hot else "disjoint",
        "t": t,
        "ktxn_s": round(t / dt / 1e3, 2),
        "rounds": int(res.rounds),
        "attempts_txn": float(np.asarray(res.attempts).mean()),
    }


_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import jax, numpy as np
    from repro import atomics
    from repro.core import distributed as dsb
    from repro.launch.mesh import make_mesh

    n, k, w = {n}, 2, {w}
    t = {t}
    strategies = {strategies}
    rows = []
    for strategy in strategies:
        for shards in {shards}:
            mesh = make_mesh((shards, 8 // shards), ("shard", "rest"))
            dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy,
                                                    p_max=1024),
                                 "shard", shards, 8)
            rng = np.random.default_rng(0)
            init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
            st = dsb.init_dist(mesh, dspec, init)
            slot = np.stack([rng.choice(n, size=w, replace=False)
                             for _ in range(t)]).astype(np.int32)
            exp = init[slot]
            des = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
            txns = atomics.make_txns(slot, exp, des, k=k)
            dsb.mcas(mesh, dspec, st, txns)          # warmup/compile
            st = dsb.init_dist(mesh, dspec, init)
            t0 = time.perf_counter()
            st, res = dsb.mcas(mesh, dspec, st, txns)
            dt = time.perf_counter() - t0
            t_local = -(-t // shards)
            wire = 4 * dsb.mcas_collective_words(dspec, t_local, w) \\
                * (shards - 1) // shards
            rows.append(dict(
                strategy=strategy, shards=shards, t=t, w=w,
                ktxn_s=round(t / dt / 1e3, 2),
                commit_rate=float(np.asarray(res.success).mean()),
                rounds=int(res.rounds),
                coll_bytes_dev_round=wire))
    print("JSON:" + json.dumps(rows))
""")


def main(quick: bool = False, tiny: bool = False):
    strategies = ["cached_me"] if tiny else STRATEGIES
    t = 8 if tiny else (32 if quick else 128)
    k = 2

    mcas_rows = []
    for w in ([2] if tiny else [1, 2, 4]):
        for n, cont in ([(64, "low")] if tiny
                        else [(max(8, w + 1), "high"), (1 << 10, "low")]):
            for policy in (POLICIES[:1] if tiny else POLICIES):
                for s in strategies:
                    mcas_rows.append(run_mcas_cell(
                        s, policy, t=t, w=w, n=n, k=k, match_frac=0.8,
                        reps=1 if tiny else 3))
                    mcas_rows[-1]["contention"] = cont
    print_table("MCAS: width x contention x strategy x backoff", mcas_rows,
                ["strategy", "policy", "w", "n", "contention", "ktxn_s",
                 "commit_rate", "rounds", "attempts_txn"])

    map_rows = []
    for s in (["cached_me"] if tiny else ["seqlock", "cached_me"]):
        for hot in ((False,) if tiny else (False, True)):
            map_rows.append(run_map_cell(s, t=min(t, 16), hot=hot))
    print_table("Transactional map: OCC conflict spectrum", map_rows,
                ["strategy", "workload", "t", "ktxn_s", "rounds",
                 "attempts_txn"])

    dist_rows = []
    if not tiny:
        script = _DIST_SCRIPT.format(
            n=1 << 8, w=2, t=16 if quick else 64,
            strategies=["cached_me"] if quick else ["seqlock", "cached_me"],
            shards=(1, 4) if quick else (1, 2, 4, 8))
        dist_rows = [dict(row, backend="cpu")
                     for row in run_cpu_child(script, timeout=3000)]
        print_table("Cross-shard MCAS (8 placeholder devices)", dist_rows,
                    ["strategy", "shards", "t", "w", "ktxn_s",
                     "commit_rate", "rounds", "coll_bytes_dev_round"])

    payload = {"mcas": mcas_rows, "map": map_rows, "mcas_dist": dist_rows}
    path = save_results("bench_txn", payload)
    print(f"\nresults -> {path}")

    # soft claim checks: contention costs rounds; hot-key map serializes
    if not tiny:
        hi = np.mean([r["rounds"] for r in mcas_rows
                      if r["contention"] == "high"])
        lo = np.mean([r["rounds"] for r in mcas_rows
                      if r["contention"] == "low"])
        print(f"[check] MCAS rounds high vs low contention: "
              f"{hi:.1f} vs {lo:.1f} -> "
              f"{'OK' if hi >= lo else 'UNEXPECTED'}")
        hot = [r for r in map_rows if r["workload"] == "hot-key"]
        if hot:
            ok = all(r["rounds"] == r["t"] for r in hot)
            print(f"[check] hot-key map fully serializes (rounds == T): "
                  f"{'OK' if ok else 'UNEXPECTED'}")
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    main(quick=args.quick, tiny=args.tiny)
