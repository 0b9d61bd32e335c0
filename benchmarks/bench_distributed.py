"""Mesh-sharded big atomics v2 (core.distributed, DESIGN.md §6).

Strategy × shard-count × contention × op-mix sweep of the
route -> apply -> return collective round, run in a subprocess with 8
placeholder devices.  Each row records throughput, the observed overflow
count, and the modeled per-device collective bytes
(`distributed.collective_words`) — the roofline cell the §Perf hillclimb
drives down (shrinking `route_capacity` cuts the wire bytes EXACTLY
proportionally; the `opt` variant shows dedup+interleave+cap/4 doing so
without overflow on the read-heavy mix).
"""

from __future__ import annotations

import sys
import textwrap

from benchmarks.common import print_table, run_cpu_child, save_results

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import jax, numpy as np
    from repro import atomics
    from repro.core import distributed as dsb
    from repro.core import engine
    from repro.launch.mesh import make_mesh

    n, k = 1 << {log_n}, 4
    p_local = {p_local}
    strategies = {strategies}
    shard_counts = {shards}
    reps = {reps}

    def batch(rng, p, upd, zipf, sync_frac):
        if zipf > 0.0:
            slots = (rng.zipf(zipf, size=p) - 1) % n
        else:
            slots = rng.integers(0, n, size=p)
        slots = slots.astype(np.int32)
        r = rng.random(p)
        kind = np.where(r < upd * 0.5, engine.STORE,
                        np.where(r < upd, engine.CAS,
                                 engine.LOAD)).astype(np.int32)
        if sync_frac > 0.0:
            s = rng.random(p) < sync_frac
            kind = np.where(s & (kind == engine.LOAD), engine.LL, kind)
            kind = np.where(s & (kind == engine.STORE), engine.SC, kind)
        expected = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
        desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
        return atomics.make_ops(kind, slots, expected, desired, k=k)

    MIXES = [("read90", 0.1, 0.0), ("upd60", 0.6, 0.0), ("sync50", 0.1, 0.5)]
    CONTENTION = [("uniform", 0.0), ("zipf1.2", 1.2)]
    rows = []
    for strategy in strategies:
        for shards in shard_counts:
            mesh = make_mesh((shards, 8 // shards), ("shard", "rest"))
            variants = [("baseline", dict())]
            if shards > 1:
                variants.append(
                    ("opt(dedup+ilv+cap/4)",
                     dict(dedup_loads=True, interleave=True,
                          route_capacity=max(p_local // 4, 8))))
            for vname, kw in variants:
                dspec = dsb.DistSpec(
                    atomics.AtomicSpec(n, k, strategy, p_max=1024),
                    "shard", shards, p_local, **kw)
                p = dspec.p_global
                for mix, upd, sync_frac in MIXES:
                    if vname != "baseline" and mix != "read90":
                        continue          # the opt levers target read traffic
                    for cont, zipf in CONTENTION:
                        rng = np.random.default_rng(0)
                        st = dsb.init_dist(mesh, dspec)
                        ctx = dsb.init_dist_ctx(mesh, dspec)
                        ops = batch(rng, p, upd, zipf, sync_frac)
                        out = dsb.apply(mesh, dspec, st, ops, ctx)
                        jax.block_until_ready(out[2])
                        t0 = time.perf_counter()
                        for _ in range(reps):
                            st, ctx, res, ovf = dsb.apply(mesh, dspec, st,
                                                          ops, ctx)
                        jax.block_until_ready(res)
                        dt = (time.perf_counter() - t0) / reps
                        # wire bytes = buffer bytes x the off-device
                        # fraction (shards-1)/shards; 0 when unsharded,
                        # matching the historical column semantics.
                        wire = 4 * dsb.collective_words(dspec) \
                            * (shards - 1) // shards
                        rows.append(dict(
                            strategy=strategy, variant=vname, shards=shards,
                            mix=mix, contention=cont, p_global=p,
                            mops_s=round(p / dt / 1e6, 3),
                            overflow=int(np.asarray(ovf).sum()),
                            coll_bytes_dev=wire))
    print("JSON:" + json.dumps(rows))
""")


def main(quick: bool = False):
    script = SCRIPT.format(
        log_n=10 if quick else 14,
        p_local=64 if quick else 256,
        strategies=["cached_me", "seqlock"] if quick
        else ["seqlock", "indirect", "cached_wf", "cached_me"],
        shards=(1, 4) if quick else (1, 2, 4, 8),
        reps=5 if quick else 10)
    rows = [dict(row, backend="cpu")
            for row in run_cpu_child(script, timeout=3000)]
    print_table("Distributed big atomics v2 (8 placeholder devices)", rows,
                ["strategy", "variant", "shards", "mix", "contention",
                 "p_global", "mops_s", "overflow", "coll_bytes_dev"])
    save_results("bench_distributed", rows)
    return rows


if __name__ == "__main__":
    main(quick="--quick" in sys.argv)
