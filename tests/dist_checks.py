"""Scenario driver for tests/test_distributed.py.

Runs in a subprocess under `XLA_FLAGS=--xla_force_host_platform_device_count=8`
(set below before jax imports) so the mesh-sharded layer executes on 8 fake
host devices.  Every scenario checks the live sharded system against the
SHARED linearizability harness (tests/oracle.py) replaying the claimed order
from `distributed.linearization_order`.

Usage:  python tests/dist_checks.py <scenario> [strategy]
Prints `DIST_OK:<scenario>` on success (the pytest wrapper asserts on it).
"""

import os
import sys
import zlib

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

_HERE = os.path.dirname(os.path.abspath(__file__))
for path in (_HERE, os.path.join(_HERE, "..", "src"),
             os.path.join(_HERE, "..")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax                                                     # noqa: E402
import numpy as np                                             # noqa: E402

from oracle import (HashOracle, MapOracle, TableOracle, TxnOracle,  # noqa: E402
                    hash_batch, mixed_batch, txn_batch)
from repro import atomics                                      # noqa: E402
from repro.core import distributed as dsb                      # noqa: E402
from repro.launch.mesh import make_mesh                        # noqa: E402

SHARD_COUNTS = (2, 4, 8)


def _mesh(shards: int):
    """All scenarios run on the same 8-device fleet; unused devices
    replicate over the spare axis."""
    return make_mesh((shards, 8 // shards), ("shard", "rest"))


def _drive_table(dspec, mesh, rng, init, steps, make_ops, msg):
    """Run `steps` batches through the sharded table, checking state,
    results, link ctx and the overflow contract against the harness."""
    st = dsb.init_dist(mesh, dspec, init)
    ctx = dsb.init_dist_ctx(mesh, dspec)
    oracle = TableOracle(dspec.n_global, dspec.inner.k, dspec.p_global,
                         initial=init)
    for step in range(steps):
        ops = make_ops(rng, oracle)
        order, ovf_ref = dsb.linearization_order(dspec, ops)
        st, ctx, res, ovf = dsb.apply(mesh, dspec, st, ops, ctx)
        np.testing.assert_array_equal(np.asarray(ovf), ovf_ref,
                                      err_msg=f"{msg} step {step}: overflow")
        oracle.step_and_check(
            ops, result=res, logical=dsb.logical(dspec, st),
            version=dsb.versions(dspec, st), ctx=ctx, order=order,
            overflow=ovf_ref, msg=f"{msg} step {step}")
    return st, ctx, oracle


def scenario_mixed(strategy: str):
    """Randomized mixed LOAD/STORE/CAS/LL/SC/VALIDATE batches vs the shared
    oracle, over shard counts {2, 4, 8}."""
    rng = np.random.default_rng(zlib.crc32(strategy.encode()))
    n, k, pl = 48, 3, 6
    for shards in SHARD_COUNTS:
        dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy, p_max=64),
                             "shard", shards, pl)
        init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
        _drive_table(
            dspec, _mesh(shards), rng, init, steps=3,
            make_ops=lambda rng, oracle: mixed_batch(
                rng, oracle.ctx, p=dspec.p_global, n=n, k=k,
                current=oracle.data),
            msg=f"mixed {strategy} shards={shards}")


def scenario_levers(strategy: str):
    """The §Perf routing levers must not change semantics: every
    dedup_loads × interleave × route_capacity combination replays against
    the shared oracle (load-heavy hot-slot batches so dedup and capacity
    overflow actually fire)."""
    n, k, shards, pl = 32, 2, 4, 8
    rng = np.random.default_rng(29)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)

    def hot_batch(rng, oracle):
        p = shards * pl
        kind = np.where(rng.random(p) < 0.7, atomics.LOAD,
                        rng.integers(0, 7, p)).astype(np.int32)
        slot = rng.integers(0, 6, p).astype(np.int32)      # hot cells
        desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
        expected = np.where((rng.random(p) < 0.5)[:, None],
                            oracle.data[slot],
                            rng.integers(0, 2 ** 32, (p, k),
                                         dtype=np.uint32)).astype(np.uint32)
        return atomics.make_ops(kind, slot, expected, desired, k=k)

    for dedup in (False, True):
        for ilv in (False, True):
            for cap in (None, 3):
                dspec = dsb.DistSpec(
                    atomics.AtomicSpec(n, k, strategy, p_max=64), "shard",
                    shards, pl, route_capacity=cap, dedup_loads=dedup,
                    interleave=ilv)
                _drive_table(dspec, _mesh(shards), rng, init, steps=2,
                             make_ops=hot_batch,
                             msg=f"levers dedup={dedup} ilv={ilv} cap={cap}")


def scenario_sync_adversary(strategy: str):
    """Cross-batch LL/SC adversaries THROUGH the routing layer: ABA (bytes
    restored on a remote shard; SC must refuse) and the lapped linker (a
    lane sleeping on its link while every other source commits)."""
    n, k, shards, pl = 16, 2, 4, 4
    mesh = _mesh(shards)
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy, p_max=64),
                         "shard", shards, pl)
    p = dspec.p_global
    rng = np.random.default_rng(5)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)

    def batch(assign):
        """assign: {lane: (kind, slot, desired_row)}"""
        kind = np.full(p, atomics.IDLE, np.int32)
        slot = np.zeros(p, np.int32)
        desired = np.zeros((p, k), np.uint32)
        for lane, (kd, sl, des) in assign.items():
            kind[lane], slot[lane] = kd, sl
            if des is not None:
                desired[lane] = des
        return atomics.make_ops(kind, slot, desired=desired, k=k)

    st = dsb.init_dist(mesh, dspec, init)
    ctx = dsb.init_dist_ctx(mesh, dspec)
    oracle = TableOracle(n, k, p, initial=init)

    def run(ops, msg):
        nonlocal st, ctx
        order, ovf = dsb.linearization_order(dspec, ops)
        assert not ovf.any()
        st, ctx, res, _ = dsb.apply(mesh, dspec, st, ops, ctx)
        ref = oracle.step_and_check(
            ops, result=res, logical=dsb.logical(dspec, st),
            version=dsb.versions(dspec, st), ctx=ctx, order=order, msg=msg)
        return np.asarray(res.success), ref

    # --- ABA: lane 0 (src 0) links cell 9 (owner shard 2); stores from a
    # DIFFERENT source restore the original bytes; SC + VALIDATE must fail.
    cell = 9
    run(batch({0: (atomics.LL, cell, None)}), "aba ll")
    original = np.array(oracle.ctx.value[0], copy=True)
    run(batch({5: (atomics.STORE, cell, (original + 1).astype(np.uint32))}),
        "aba store B")
    run(batch({5: (atomics.STORE, cell, original)}), "aba store A")
    np.testing.assert_array_equal(
        np.asarray(dsb.logical(dspec, st))[cell], original)  # bytes match
    succ, _ = run(batch({0: (atomics.VALIDATE, cell, None)}), "aba validate")
    assert not succ[0], "VALIDATE must fail after remote A->B->A"
    succ, _ = run(batch({0: (atomics.SC, cell, original)}), "aba sc")
    assert not succ[0], "SC must fail after remote A->B->A"

    # --- Lapped linker: lane 0 links cell 0; every other lane (across all
    # sources) LLs then SCs it in turn; lane 0's eventual SC must fail.
    run(batch({0: (atomics.LL, 0, None)}), "lap ll0")
    for lane in range(1, p):
        run(batch({lane: (atomics.LL, 0, None)}), f"lap ll{lane}")
        succ, _ = run(batch({lane: (atomics.SC, 0,
                                    np.full(k, lane, np.uint32))}),
                      f"lap sc{lane}")
        assert succ[lane], f"fresh link SC of lane {lane} must succeed"
    succ, _ = run(batch({0: (atomics.SC, 0, np.zeros(k, np.uint32))}),
                  "lap sc0")
    assert not succ[0], "lapped linker's SC must fail"


def scenario_overflow(strategy: str):
    """The all_to_all capacity contract: lanes beyond route_capacity per
    (src, dst) pair surface in the overflow mask with success=False and
    leave every shard's table byte-identical to the oracle that skips them
    — never silently dropped, never corrupting."""
    n, k, shards, pl, cap = 32, 2, 4, 8, 3
    mesh = _mesh(shards)
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy, p_max=64),
                         "shard", shards, pl, route_capacity=cap)
    p = dspec.p_global
    rng = np.random.default_rng(7)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    st = dsb.init_dist(mesh, dspec, init)
    oracle = TableOracle(n, k, p, initial=init)

    # All 8 lanes of src 0 hit shard 0 (slots 0..7), alternating STORE/LOAD;
    # srcs 1..3 send two lanes each to shard 0 (within cap) plus local ops.
    kind = np.full(p, atomics.IDLE, np.int32)
    slot = np.zeros(p, np.int32)
    desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    for lane in range(pl):
        kind[lane] = atomics.STORE if lane % 2 == 0 else atomics.LOAD
        slot[lane] = lane                      # owner shard 0
    for src in range(1, shards):
        base = src * pl
        kind[base] = atomics.STORE
        slot[base] = src                       # owner shard 0
        kind[base + 1] = atomics.LOAD
        slot[base + 1] = src + 8 * src         # spread
    ops = atomics.make_ops(kind, slot, desired=desired, k=k)

    order, ovf_ref = dsb.linearization_order(dspec, ops)
    # by construction: src 0's lanes 3..7 exceed cap=3 toward shard 0
    assert list(np.nonzero(ovf_ref)[0]) == [3, 4, 5, 6, 7]
    st, ctx, res, ovf = dsb.apply(mesh, dspec, st, ops)
    np.testing.assert_array_equal(np.asarray(ovf), ovf_ref)
    assert not np.asarray(res.success)[ovf_ref].any(), \
        "overflowed lanes must report success=False"
    # table state matches the oracle that executes ONLY the fitting lanes:
    # the overflowed STOREs (lanes 4, 6) left no trace anywhere.
    oracle.step_and_check(ops, result=res, logical=dsb.logical(dspec, st),
                          version=dsb.versions(dspec, st), order=order,
                          overflow=ovf_ref, msg="overflow contract")


def scenario_plugin(strategy_unused: str):
    """A strategy registered HERE (never imported by core/distributed.py)
    runs sharded unchanged — the registry is the only coupling."""

    class PlainCloneDist(atomics.StrategyImpl):
        name = "dist_plugin_check"

    atomics.register_strategy(PlainCloneDist(), overwrite=True)
    rng = np.random.default_rng(23)
    n, k, shards, pl = 24, 2, 4, 4
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, "dist_plugin_check",
                                            p_max=32), "shard", shards, pl)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    _drive_table(
        dspec, _mesh(shards), rng, init, steps=3,
        make_ops=lambda rng, oracle: mixed_batch(
            rng, oracle.ctx, p=dspec.p_global, n=n, k=k,
            current=oracle.data),
        msg="plugin shards=4")


def scenario_hash(strategy: str):
    """Key-owner-routed sharded CacheHash vs the dict-model oracle over
    shard counts {2, 4, 8}, plus the capacity-overflow contract on a
    single hot key."""
    rng = np.random.default_rng(zlib.crc32(strategy.encode()) ^ 0x5A5A)
    for shards in SHARD_COUNTS:
        hs = atomics.HashSpec(64, vw=1, strategy=strategy, p_max=64)
        dspec = dsb.DistSpec(hs, "shard", shards, 6)
        mesh = _mesh(shards)
        st = dsb.init_dist(mesh, dspec)
        oracle = HashOracle(vw=1)
        for step in range(3):
            ops = hash_batch(rng, p=dspec.p_global, key_space=40, vw=1)
            order, ovf_ref = dsb.linearization_order(dspec, ops)
            st, res, ovf = dsb.apply_hash(mesh, dspec, st, ops)
            np.testing.assert_array_equal(
                np.asarray(ovf), ovf_ref,
                err_msg=f"hash shards={shards} step {step}: overflow")
            oracle.step_and_check(
                ops, result=res, items=dsb.hash_items(dspec, st),
                order=order, overflow=ovf_ref,
                msg=f"hash {strategy} shards={shards} step {step}")

    # hot-key overflow: every lane of src 0 inserts the SAME key with cap=2
    shards, pl, cap = 4, 6, 2
    hs = atomics.HashSpec(64, vw=1, strategy=strategy, p_max=64)
    dspec = dsb.DistSpec(hs, "shard", shards, pl, route_capacity=cap)
    mesh = _mesh(shards)
    st = dsb.init_dist(mesh, dspec)
    kind = np.full(dspec.p_global, atomics.IDLE, np.int32)
    kind[:pl] = atomics.INSERT
    keys = np.full(dspec.p_global, 12345, np.uint32)
    vals = np.arange(dspec.p_global, dtype=np.uint32)[:, None]
    from repro.core import cachehash as ch
    ops = ch.make_hash_ops(kind, keys, vals, vw=1)
    order, ovf_ref = dsb.linearization_order(dspec, ops)
    assert ovf_ref.sum() == pl - cap
    st, res, ovf = dsb.apply_hash(mesh, dspec, st, ops)
    np.testing.assert_array_equal(np.asarray(ovf), ovf_ref)
    assert not np.asarray(res.found)[ovf_ref].any()
    oracle = HashOracle(vw=1)
    oracle.step_and_check(ops, result=res, items=dsb.hash_items(dspec, st),
                          order=order, overflow=ovf_ref, msg="hash overflow")


def scenario_mcas(strategy: str):
    """Cross-shard MCAS (two-round prepare/commit collective) vs the
    TxnOracle replaying the claimed whole-transaction order, shard counts
    {2, 4, 8}, widths {1, 2, 3} — cross-shard transactions arise naturally
    (random slots over all shards' cells), plus an explicit one."""
    from repro.txn import mcas as txn_mcas

    rng = np.random.default_rng(zlib.crc32(strategy.encode()) ^ 0x7777)
    n, k = 24, 2
    for shards, w in zip(SHARD_COUNTS, (1, 2, 3)):
        mesh = _mesh(shards)
        dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy, p_max=64),
                             "shard", shards, 8)
        init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
        st = dsb.init_dist(mesh, dspec, init)
        oracle = TxnOracle(n, k, initial=init)
        for step in range(3):
            txns = txn_batch(rng, t=8, w=w, n=n, k=k, current=oracle.data)
            st, res = dsb.mcas(mesh, dspec, st, txns)
            oracle.step_and_check(
                txns, result=res, logical=dsb.logical(dspec, st),
                version=dsb.versions(dspec, st),
                msg=f"mcas {strategy} shards={shards} w={w} step {step}")

    # explicit cross-shard all-or-nothing: one txn spans all 4 shards and
    # one stale lane on the LAST shard aborts the whole thing.
    shards, w = 4, 4
    mesh = _mesh(shards)
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, strategy, p_max=64),
                         "shard", shards, 8)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    st = dsb.init_dist(mesh, dspec, init)
    span = np.asarray([[0, 6, 12, 18]], np.int32)     # one cell per shard
    exp = init[span[0]][None].copy()
    exp[0, 3] += 1                                     # stale on shard 3
    txns = atomics.make_txns(span, exp,
                             np.full((1, w, k), 5, np.uint32), k=k)
    st, res = dsb.mcas(mesh, dspec, st, txns)
    assert not bool(np.asarray(res.success)[0])
    np.testing.assert_array_equal(np.asarray(dsb.logical(dspec, st)), init)
    # fix the comparand: the same txn commits on every shard at once
    txns = atomics.make_txns(span, init[span[0]][None],
                             np.full((1, w, k), 5, np.uint32), k=k)
    st, res = dsb.mcas(mesh, dspec, st, txns)
    assert bool(np.asarray(res.success)[0])
    got = np.asarray(dsb.logical(dspec, st))
    np.testing.assert_array_equal(got[span[0]], np.full((w, k), 5))


def scenario_txnmap(strategy: str):
    """Transactional map over the key-owner-routed sharded CacheHash:
    read/write sets spanning shards commit serializably (MapOracle),
    including the everyone-increments-one-counter conflict storm."""
    from repro.txn import map as txn_map

    def fn(rv, rf):
        return rv.sum(axis=1, keepdims=True) + 1

    rng = np.random.default_rng(zlib.crc32(strategy.encode()) ^ 0x3333)
    for shards in (2, 4):
        mesh = _mesh(shards)
        hs = atomics.HashSpec(64, vw=1, strategy=strategy, p_max=64)
        dspec = dsb.DistSpec(hs, "shard", shards, 4)
        st = dsb.init_dist(mesh, dspec)
        oracle = MapOracle(vw=1)
        t, r, w = 5, 2, 2
        for step in range(2):
            txns = txn_map.make_map_txns(
                rng.integers(0, 30, (t, r)).astype(np.uint32),
                np.stack([rng.choice(30, size=w, replace=False)
                          for _ in range(t)]).astype(np.uint32),
                read_mask=rng.random((t, r)) < 0.8,
                write_del=rng.random((t, w)) < 0.2)
            st, res = txn_map.transact_dist(mesh, dspec, st, txns,
                                            _map_fn_copy)
            oracle.step_and_check(
                txns, _map_fn_copy, result=res,
                items=dsb.hash_items(dspec, st),
                msg=f"txnmap {strategy} shards={shards} step {step}")
        # conflict storm: T txns increment one counter key serializably
        t = 4
        txns = txn_map.make_map_txns(np.full((t, 1), 17, np.uint32),
                                     np.full((t, 1), 17, np.uint32))
        st, res = txn_map.transact_dist(mesh, dspec, st, txns, fn)
        assert int(res.rounds) == t
        oracle.step_and_check(txns, fn, result=res,
                              items=dsb.hash_items(dspec, st),
                              msg=f"txnmap storm shards={shards}")
        assert oracle.model[17][0] == t


def _map_fn_copy(rv, rf):
    return rv


def scenario_txn_plugin(strategy_unused: str):
    """A strategy registered HERE runs cross-shard MCAS and the sharded
    transactional map unchanged (the txn layer is registry-dispatched all
    the way through the collective)."""
    from repro.txn import map as txn_map

    class PlainCloneTxnDist(atomics.StrategyImpl):
        name = "dist_txn_plugin_check"

    atomics.register_strategy(PlainCloneTxnDist(), overwrite=True)
    rng = np.random.default_rng(41)
    n, k, shards, w = 24, 2, 4, 2
    mesh = _mesh(shards)
    dspec = dsb.DistSpec(
        atomics.AtomicSpec(n, k, "dist_txn_plugin_check", p_max=64),
        "shard", shards, 8)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    st = dsb.init_dist(mesh, dspec, init)
    oracle = TxnOracle(n, k, initial=init)
    for step in range(2):
        txns = txn_batch(rng, t=8, w=w, n=n, k=k, current=oracle.data)
        st, res = dsb.mcas(mesh, dspec, st, txns)
        oracle.step_and_check(
            txns, result=res, logical=dsb.logical(dspec, st),
            version=dsb.versions(dspec, st),
            msg=f"txn plugin mcas step {step}")
    hs = atomics.HashSpec(64, vw=1, strategy="dist_txn_plugin_check",
                          p_max=64)
    hdspec = dsb.DistSpec(hs, "shard", shards, 4)
    hst = dsb.init_dist(mesh, hdspec)
    txns = txn_map.make_map_txns(np.full((3, 1), 8, np.uint32),
                                 np.full((3, 1), 8, np.uint32))

    def fn(rv, rf):
        return rv.sum(axis=1, keepdims=True) + 1

    hst, res = txn_map.transact_dist(mesh, hdspec, hst, txns, fn)
    MapOracle(vw=1).step_and_check(txns, fn, result=res,
                                   items=dsb.hash_items(hdspec, hst),
                                   msg="txn plugin map")


def scenario_serving(strategy: str):
    """The serving engine with a mesh: sharded page table + sharded
    admission/slot rings must produce tokens identical to the single-device
    engine (one fused program per decode step, executed per shard)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.serving import Request, ServingEngine

    cfg = dataclasses.replace(get_config("deepseek_7b", reduced=True),
                              param_dtype="float32",
                              compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 9).astype(np.int32),
               rng.integers(0, cfg.vocab, 5).astype(np.int32)]
    n_new = 3

    def serve(mesh):
        eng = ServingEngine(cfg, params, max_batch=2, n_pages=16,
                            page_size=4, max_pages_per_seq=4,
                            strategy=strategy, mesh=mesh)
        for rid, prompt in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=prompt,
                               max_new_tokens=n_new))
        out = eng.run_to_completion(max_steps=40)
        # both slots decode together: n_new - 1 fused steps, 1 dispatch each
        assert eng.dispatch_count == n_new - 1, eng.dispatch_count
        return out

    want = serve(None)
    got = serve(_mesh(2))
    assert got == want, (got, want)
    assert all(len(v) == n_new for v in got.values())


def scenario_twolevel(strategy: str):
    """Hierarchical two-level routing (DistSpec.n_nodes > 1): intra-node
    combine over `axis` then ONE cross-node all_to_all over `node_axis`,
    replayed against the shared oracle over interleave × capacity
    variants (the tight caps force overflow at BOTH hops)."""
    rng = np.random.default_rng(zlib.crc32(strategy.encode()) ^ 0x2E11)
    n, k, pl = 48, 3, 4
    mesh = make_mesh((2, 4), ("node", "shard"))
    for ilv in (False, True):
        for caps in ({}, dict(route_capacity=3, node_capacity=5)):
            dspec = dsb.DistSpec(
                atomics.AtomicSpec(n, k, strategy, p_max=64), "shard", 8,
                pl, n_nodes=2, node_axis="node", interleave=ilv, **caps)
            init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
            _drive_table(
                dspec, mesh, rng, init, steps=3,
                make_ops=lambda rng, oracle: mixed_batch(
                    rng, oracle.ctx, p=dspec.p_global, n=n, k=k,
                    current=oracle.data),
                msg=f"2level ilv={ilv} capped={bool(caps)}")


def scenario_executor(strategy: str):
    """The oversubscribed executor (ISSUE 7 acceptance): S in {2, 4, 8}
    streams share one 8-shard table with in-flight budget 4; a shard loss
    injected MID-ROUND forces checkpoint-restore + reshard onto the
    survivors + journal replay, and the full multi-stream history —
    including across the recovery boundary — must replay through ONE
    sequential oracle."""
    from oracle import replay_executor_history
    from repro.runtime import (DistTarget, Executor, Fault, FaultInjector,
                               StragglerWatchdog, SyntheticStream)

    n, k = 32, 2

    def factory(n_surviving):
        s = 1
        while s * 2 <= n_surviving and n % (s * 2) == 0:
            s *= 2
        mesh = make_mesh((s, 8 // s), ("shard", "rest"))
        return mesh, dsb.DistSpec(
            atomics.AtomicSpec(n, k, strategy, p_max=64), "shard", s,
            32 // s)

    rng = np.random.default_rng(zlib.crc32(strategy.encode()) ^ 0xE7)
    for n_streams in (2, 4, 8):
        init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
        mesh0, dspec0 = factory(8)
        target = DistTarget(mesh0, dspec0, init, mesh_factory=factory)
        width = dspec0.p_global
        streams = [SyntheticStream(f"s{i}", seed=1000 + 10 * n_streams + i,
                                   n=n, k=k, width=width, n_batches=3,
                                   hot_cells=4, hot_frac=0.3)
                   for i in range(n_streams)]
        inj = FaultInjector([Fault(round=2, kind="shard_loss", shard=5,
                                   after_issues=1)])
        ex = Executor(target, streams, slots=1, oversubscription=4,
                      watchdog=StragglerWatchdog(n_hosts=n_streams),
                      injector=inj, checkpoint_every=2)
        rep = ex.run()
        assert rep["recoveries"], rep
        assert rep["recoveries"][0]["n_shards"] < 8
        assert target.dspec.p_global == width      # lane layout preserved
        oracle = replay_executor_history(n, k, [width] * n_streams,
                                         ex.history, initial=init)
        np.testing.assert_array_equal(
            oracle.data, np.asarray(dsb.logical(target.dspec, target.state)),
            err_msg=f"executor S={n_streams}: final logical")
        np.testing.assert_array_equal(
            oracle.version,
            np.asarray(dsb.versions(target.dspec, target.state)),
            err_msg=f"executor S={n_streams}: final versions")


def scenario_elastic(strategy: str):
    """Elastic round-trips on the 8-device fixture.  (a) The big-atomic
    table reshards 8 -> 6 -> 4 -> 8 with logical values AND versions
    preserved at every hop — an LL link taken BEFORE the trip commits
    after it.  (b) The (params, opt) training state reshards through the
    same shrink/grow chain bit-identically, with `mesh_plan` reporting
    (never silently truncating) the devices each geometry drops."""
    from repro.runtime import elastic_mesh, mesh_plan, reshard_dist, \
        reshard_state

    n, k = 48, 2
    rng = np.random.default_rng(11)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)

    def geo(s):
        mesh = make_mesh((s,), ("shard",), devices=jax.devices()[:s])
        return mesh, dsb.DistSpec(
            atomics.AtomicSpec(n, k, strategy, p_max=64), "shard", s, 8)

    mesh, dspec = geo(8)
    st = dsb.init_dist(mesh, dspec, init)
    ctx = dsb.init_dist_ctx(mesh, dspec)
    # lane 0 links cell 5; lane 1 bumps cell 7 so versions are non-trivial
    kind = np.full(dspec.p_global, atomics.IDLE, np.int32)
    slot = np.zeros(dspec.p_global, np.int32)
    desired = np.zeros((dspec.p_global, k), np.uint32)
    kind[0], slot[0] = atomics.LL, 5
    kind[1], slot[1], desired[1] = atomics.STORE, 7, 77
    st, ctx, _, _ = dsb.apply(mesh, dspec, st,
                              atomics.make_ops(kind, slot, desired=desired,
                                               k=k), ctx)
    vals = np.asarray(dsb.logical(dspec, st))
    vers = np.asarray(dsb.versions(dspec, st))
    assert vers[7] == 2 and vers.sum() == 2
    for s in (6, 4, 8):
        mesh2, dspec2 = geo(s)
        st = reshard_dist(dspec, st, dspec2, mesh2)
        mesh, dspec = mesh2, dspec2
        np.testing.assert_array_equal(np.asarray(dsb.logical(dspec, st)),
                                      vals, err_msg=f"reshard->{s}: values")
        np.testing.assert_array_equal(np.asarray(dsb.versions(dspec, st)),
                                      vers, err_msg=f"reshard->{s}: versions")
    # versions survived the whole trip, so the pre-trip link commits
    kind = np.full(dspec.p_global, atomics.IDLE, np.int32)
    kind[0], slot[0], desired[0] = atomics.SC, 5, 55
    st, ctx, res, _ = dsb.apply(mesh, dspec, st,
                                atomics.make_ops(kind, slot,
                                                 desired=desired, k=k), ctx)
    assert bool(np.asarray(res.success)[0]), \
        "LL link must survive the 8->6->4->8 reshard round-trip"
    assert (np.asarray(dsb.logical(dspec, st))[5] == 55).all()

    # (b) training state through the same chain
    from repro.configs import get_config
    from repro.launch.steps import init_train_state
    from repro.optim import AdamWConfig

    cfg = get_config("deepseek_7b", reduced=True)
    params, opt = init_train_state(cfg, AdamWConfig(warmup=1, total_steps=2),
                                   0)
    want = [np.asarray(x) for x in jax.tree.leaves(params)]
    assert mesh_plan(6, model_parallel=2, global_batch=2).dropped == 4
    assert mesh_plan(6, model_parallel=2).dropped == 0
    for n_dev in (8, 6, 4, 8):
        m = elastic_mesh(n_dev, model_parallel=2, global_batch=2)
        params, opt = reshard_state((params, opt), cfg, m)
    got = [np.asarray(x) for x in jax.tree.leaves(params)]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    lead = jax.tree.leaves(params)[1]
    assert len(lead.sharding.device_set) in (2, 4, 8)


def _traced_names(fn, log_dir: str):
    """(scopes, spans) the benchmark's trace reduction finds in a traced
    call of `fn`, inside the harness's own window spans."""
    from bench import devtrace
    devtrace.start(log_dir)
    with jax.profiler.TraceAnnotation(devtrace.SPAN_DISPATCH):
        out = fn()
    with jax.profiler.TraceAnnotation(devtrace.SPAN_FETCH):
        jax.block_until_ready(out)
    devtrace.stop()
    summary = devtrace.reduce(devtrace.load(log_dir), len(jax.devices()))
    return set(summary.scope_ns), set(summary.span_self_ns)


def scenario_round(mode: str):
    """The sharded round on the engine's kernels, in one engine-kernel mode
    (`pallas`, interpreted here, or `xla`): on a stream like the
    benchmark's YCSB-A (50/50 LOAD/STORE, Zipf 0.99 ranks, so a hot record
    on one shard) every shard runs `engine.round_for` and the results equal
    the shared oracle's and the benchmark's reference (`TableRef`).  The
    table is built shard by shard from a function of global cell ids,
    equal to the build from the same array; read back shard by shard,
    `gather` equals `logical` / `versions` at the touched records and the
    blocked untouched count equals the whole-table count.  A traced call
    holds the `dist.*` scopes and the three `atomics.dist.apply` spans."""
    import tempfile

    import jax.numpy as jnp
    from bench import gen, reference
    from bench.systems import dist_table

    os.environ["BIGATOMIC_ENGINE_KERNEL"] = mode
    n, k, shards, pl, seed = 4096, 8, 4, 16, 2 ** 33 + 17
    mesh = _mesh(shards)
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, "cached_me",
                                            p_max=shards * pl),
                         "shard", shards, pl)
    start = gen.cell_words(seed, np.arange(n), k)
    st = dsb.init_dist(mesh, dspec,
                       lambda cells: gen.cell_words(seed, cells, k, jnp))
    for got, want in zip(jax.tree.leaves(st),
                         jax.tree.leaves(dsb.init_dist(mesh, dspec, start))):
        assert got.sharding.is_equivalent_to(want.sharding, got.ndim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    traffic = {"mix": {"LOAD": 0.5, "STORE": 0.5}, "keys": "zipf",
               "theta": 0.99, "pool_batches": 4}
    pool = gen.draw(traffic, space=n, lanes=dspec.p_global, width=k,
                    seed=seed, codes=dist_table.CODES)
    slots = pool.index.astype(np.int32)
    hot = np.bincount(slots.ravel(), minlength=n).argmax()
    assert np.sum(slots == hot) >= 2 * len(pool), "no hot record"
    ops = [atomics.make_ops(pool.kind[b], slots[b], None, pool.value[b], k=k)
           for b in range(len(pool))]
    jaxpr = str(jax.make_jaxpr(dsb._build_table_apply(mesh, dspec, mode))(
        st.local, None, *ops[0]))
    assert ("pallas_call" in jaxpr) == (mode == "pallas"), mode

    oracle = TableOracle(n, k, dspec.p_global, initial=start)
    ref = reference.TableRef(seed, dict(enumerate(slots)), k,
                             dist_table.CODES)
    for b, batch in enumerate(ops):
        order, ovf_ref = dsb.linearization_order(dspec, batch)
        assert not ovf_ref.any()
        st, _, res, ovf = dsb.apply(mesh, dspec, st, batch, donate=True)
        assert not np.asarray(ovf).any()
        oracle.step_and_check(batch, result=res,
                              logical=dsb.logical(dspec, st),
                              version=dsb.versions(dspec, st), order=order,
                              msg=f"round {mode} batch {b}")
        want_v, want_s = ref.apply(b, pool.kind[b], pool.value[b])
        np.testing.assert_array_equal(np.asarray(res.value), want_v)
        np.testing.assert_array_equal(np.asarray(res.success), want_s)

    touched = np.unique(slots)
    vals, vers = dsb.gather(mesh, dspec, st, touched)
    np.testing.assert_array_equal(np.asarray(vals), oracle.data[touched])
    np.testing.assert_array_equal(np.asarray(vers), oracle.version[touched])
    want_v, want_ver = ref.read(touched)
    np.testing.assert_array_equal(np.asarray(vals), want_v)
    np.testing.assert_array_equal(np.asarray(vers), want_ver)

    spec = dist_table.Spec(mesh, dspec)
    victim = next(i for i in range(n) if i not in set(touched.tolist()))
    for state, planted in ((st, 0),
                           (dist_table.change_record(st, victim, "data"), 1)):
        moved = (np.any(np.asarray(dsb.logical(dspec, state)) != start,
                        axis=1) | (np.asarray(dsb.versions(dspec, state))
                                   != 0))
        assert moved[touched].any()
        moved[touched] = False
        assert int(moved.sum()) == planted
        for block in (64, 1 << 22):
            assert dist_table.untouched_mismatches(
                spec, seed, state, touched, block=block) == planted, block

    if mode == "xla":
        with tempfile.TemporaryDirectory() as log_dir:
            scopes, spans = _traced_names(
                lambda: dsb.apply(mesh, dspec, st, ops[0]), log_dir)
        assert {dsb.SCOPE_ROUTE, dsb.SCOPE_RETURN} <= scopes, scopes
        assert {dsb.SPAN_APPLY, dsb.SPAN_VALIDATE,
                dsb.SPAN_LAUNCH} <= spans, spans


SCENARIOS = {
    "mixed": scenario_mixed,
    "levers": scenario_levers,
    "sync_adversary": scenario_sync_adversary,
    "overflow": scenario_overflow,
    "plugin": scenario_plugin,
    "hash": scenario_hash,
    "serving": scenario_serving,
    "mcas": scenario_mcas,
    "txnmap": scenario_txnmap,
    "txn_plugin": scenario_txn_plugin,
    "twolevel": scenario_twolevel,
    "executor": scenario_executor,
    "elastic": scenario_elastic,
    "round": scenario_round,
}


def main(argv):
    scenario = argv[1]
    strategy = argv[2] if len(argv) > 2 else \
        atomics.DEFAULT_STRATEGY
    SCENARIOS[scenario](strategy)
    print(f"DIST_OK:{scenario}")


if __name__ == "__main__":
    main(sys.argv)
