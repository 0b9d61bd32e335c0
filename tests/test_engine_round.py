"""The fused engine round (kernels/engine_round.py) vs the pure-XLA
`engine.linearize` reference: interpret-mode kernel equivalence over mixed
op-kind batches x all four lock-free strategies x collision spectra, the
fast-path predicate's false-positive safety, the plug-in fallback path, and
the apply-layer re-trace/donation contracts (ISSUE 5)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import atomics
from repro.core import engine
from repro.kernels import engine_round

STRATEGIES = ["seqlock", "indirect", "cached_wf", "cached_me"]
SPECTRA = ["none", "low", "all_same"]
ALL_KINDS = [atomics.LOAD, atomics.STORE, atomics.CAS, atomics.IDLE,
             atomics.LL, atomics.SC, atomics.VALIDATE]


def make_table(n, k, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    ver = (rng.integers(0, 8, n) * 2).astype(np.uint32)
    return jnp.asarray(data), jnp.asarray(ver)


def make_batch(rng, n, k, p, spectrum, kinds=ALL_KINDS, data=None, ver=None):
    """A mixed batch + a LinkCtx with a mix of live/stale/mismatched links."""
    kind = rng.choice(np.asarray(kinds), p).astype(np.int32)
    if spectrum == "none":
        assert p <= n, "collision-free spectrum needs p <= n"
        slots = rng.choice(n, p, replace=False).astype(np.int32)
    elif spectrum == "low":
        slots = rng.integers(0, max(n // 8, 2), p).astype(np.int32)
    else:                                   # all_same: worst-case contention
        slots = np.full(p, rng.integers(0, n), np.int32)
    expected = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    if data is not None:                    # let ~half the CASes succeed
        cur = np.asarray(data)
        for i in range(p):
            if rng.random() < 0.5:
                expected[i] = cur[slots[i]]
    desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    ops = atomics.make_ops(kind, slots, expected, desired, k=k)

    # links: ~70% of SC/VALIDATE lanes name their own slot with the live
    # version (they can commit), the rest are stale or name another cell
    cslot = np.where(rng.random(p) < 0.7, slots,
                     rng.integers(-1, n, p)).astype(np.int32)
    vnow = np.asarray(ver)[np.clip(cslot, 0, n - 1)]
    cver = np.where(rng.random(p) < 0.8, vnow, vnow + 2).astype(np.uint32)
    ctx = engine.LinkCtx(
        slot=jnp.asarray(cslot), version=jnp.asarray(cver),
        value=jnp.asarray(
            rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)),
        linked=jnp.asarray(rng.random(p) < 0.8))
    return ops, ctx


def assert_rounds_equal(ref, out, label=""):
    names = ["data", "version", "ctx.slot", "ctx.version", "ctx.value",
             "ctx.linked", "res.value", "res.success", "rounds", "n_updates",
             "n_loads", "n_cas_fail", "n_raced_loads", "n_dirty_cells"]
    for name, a, b in zip(names, jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{label}: fused round diverges from linearize on {name}")


# ---------------------------------------------------------------------------
# Kernel round vs linearize: bit-identical on every in-contract batch.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["xla", "pallas"])
@pytest.mark.parametrize("spectrum", SPECTRA)
def test_round_matches_linearize_mixed_kinds(mode, spectrum):
    n, k, p = 32, 4, 24
    rng = np.random.default_rng(hash((mode, spectrum)) % 2 ** 31)
    data, ver = make_table(n, k)
    round_fn = engine_round.make_round(n, k, mode=mode, interpret=True)
    for trial in range(3):
        ops, ctx = make_batch(rng, n, k, p, spectrum, data=data, ver=ver)
        ref = engine.linearize(data, ver, ctx, ops)
        out = round_fn(data, ver, ctx, ops)
        assert_rounds_equal(ref, out, f"{mode}/{spectrum}/trial{trial}")
        data, ver = ref[0], ref[1]          # chain batches across state


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_round_matches_linearize_odd_width_and_padding(mode):
    """p not a multiple of the lane tile and k=1 exercise kernel padding."""
    n, k, p = 16, 1, 11
    rng = np.random.default_rng(5)
    data, ver = make_table(n, k, seed=5)
    round_fn = engine_round.make_round(n, k, mode=mode, interpret=True,
                                       block=4)
    ops, ctx = make_batch(rng, n, k, p, "low", data=data, ver=ver)
    assert_rounds_equal(engine.linearize(data, ver, ctx, ops),
                        round_fn(data, ver, ctx, ops), "padding")


@pytest.mark.parametrize("n,k", [(300, 4), (200, 3), (260, 128), (130, 130)])
@pytest.mark.parametrize("spectrum", ["none", "low"])
def test_round_matches_linearize_table_views(n, k, spectrum):
    """The kernels see the table through lane-dense views: cells past the
    first 128-lane window, n not a multiple of 128, a width padded to
    Mosaic's row tiling (k=3), and rows of k >= 128 words (one or more
    whole 128-lane rows per cell, zero-padded)."""
    p = 24
    rng = np.random.default_rng(n * 1000 + k)
    data, ver = make_table(n, k, seed=n + k)
    round_fn = engine_round.make_round(n, k, mode="pallas", interpret=True)
    for trial in range(2):
        ops, ctx = make_batch(rng, n, k, p, spectrum, data=data, ver=ver)
        ref = engine.linearize(data, ver, ctx, ops)
        assert_rounds_equal(ref, round_fn(data, ver, ctx, ops),
                            f"n={n}/k={k}/{spectrum}/trial{trial}")
        data, ver = ref[0], ref[1]


def _zipf_slots(rng, n, p, theta=0.99):
    """Zipf(theta) ranks over n cells, spread by an odd multiply."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -theta
    ranks = rng.choice(n, p, p=w / w.sum())
    return ((ranks * 2654435761 + 17) % n).astype(np.int32)


def _slow_case(case, rng):
    """(n, k, slots) of a colliding batch that exercises one part of the
    slow kernel's window pipeline (`engine_round._slow_meta`)."""
    ring, ahead = engine_round._RING, engine_round._AHEAD
    if case == "one_window":
        # distinct written cells of one 128-cell window, adjacent once
        # sorted, with a second window on either side
        return 512, 4, np.asarray([130, 131, 131, 135, 200, 255, 129, 140,
                                   3, 300, 131, 254], np.int32)
    if case == "hot_cell":
        # one cell with more lanes than a lane tile and the lookahead, and
        # more windows than ring slots, so segments span grid steps and
        # the ring wraps
        hot = max(engine_round.SLOW_BLOCK, ahead) + 5
        windows = 2 * ring + 3
        n = 128 * windows
        others = rng.integers(0, 128, windows) + 128 * np.arange(windows)
        slots = np.concatenate([np.full(hot, 128 * (windows // 2) + 7),
                                others, others[::3]])
        return n, 2, rng.permutation(slots).astype(np.int32)
    if case == "ragged_n":
        # n = 300: the last window holds live cells 256..299 beside dead
        # (slot n) and out-of-range lanes, which must not split its segment
        n = 300
        slots = np.concatenate([rng.integers(250, n, 20), [n, n + 3, 299],
                                rng.integers(0, n, 16), [-1, 298, 298]])
        return n, 4, rng.permutation(slots).astype(np.int32)
    if case in ("rows_k128", "rows_k130"):
        # one cell per window; neighbouring cells share a version row
        k = 128 if case == "rows_k128" else 130
        slots = np.concatenate([np.arange(0, 20), np.arange(5, 12),
                                [130, 131, 131, 259, 140]])
        return 260, k, rng.permutation(slots).astype(np.int32)
    assert case == "zipf"
    return 4096, 2, _zipf_slots(rng, 4096, 256)


@pytest.mark.parametrize("case", ["one_window", "hot_cell", "ragged_n",
                                  "rows_k128", "rows_k130", "zipf"])
def test_slow_kernel_window_segments(case):
    """The slow kernel against `linearize` on batches that stress its
    window segments: a segment per window (not per cell), lookahead DMAs
    through a ring that persists across lane tiles, live-only flags, and
    version rows handed between row-window segments."""
    rng = np.random.default_rng(sum(map(ord, case)))
    n, k, slots = _slow_case(case, rng)
    p = len(slots)
    data, ver = make_table(n, k, seed=p)
    kinds = rng.choice(np.asarray([atomics.LOAD, atomics.STORE, atomics.CAS,
                                   atomics.LL, atomics.SC, atomics.VALIDATE,
                                   atomics.IDLE]), p,
                       p=[.3, .3, .1, .1, .1, .05, .05]).astype(np.int32)
    kinds[:2] = atomics.STORE           # a write: never the fast tier
    # Out-of-range active lanes are out of contract: the kernel makes them
    # failed no-ops, so `linearize` sees them idle.
    oor = (slots < 0) | (slots >= n)
    kinds[oor] = np.where(np.arange(p)[oor] % 2, atomics.LOAD, atomics.STORE)
    ops, ctx = make_batch(rng, n, k, p, "none" if p <= n else "low",
                          data=data, ver=ver)
    ops = ops._replace(kind=jnp.asarray(kinds), slot=jnp.asarray(slots))
    in_contract = ops._replace(
        kind=jnp.asarray(np.where(oor, atomics.IDLE, kinds)))
    assert not bool(engine_round.fast_path_ok(n, ops))
    round_fn = engine_round.make_round(n, k, mode="pallas", interpret=True)
    for trial in range(2):
        ref = engine.linearize(data, ver, ctx, in_contract)
        out = round_fn(data, ver, ctx, ops)
        if oor.any():
            res = out[3]
            assert not np.asarray(res.success)[oor].any()
            np.testing.assert_array_equal(np.asarray(res.value)[oor], 0)
            lanes = lambda a: np.asarray(a)[~oor]      # noqa: E731
            ref = (*ref[:3], jax.tree.map(lanes, ref[3]))
            out = (*out[:3], jax.tree.map(lanes, out[3]))
        assert_rounds_equal(ref, out, f"{case}/trial{trial}")
        data, ver, ctx = ref[0], ref[1], ref[2]

    # The counter's definition is the kernel's segment count.
    meta = engine_round._slow_meta(
        n, k, jnp.sort(jnp.where(ops.kind != atomics.IDLE, ops.slot, n)),
        ops.kind, jnp.ones(p, jnp.uint32))
    starts = np.sum((np.asarray(meta[:, 5]) & engine_round._SEG_START) != 0)
    assert starts == int(engine_round.slow_windows(n, k, ops))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("spectrum", SPECTRA)
def test_apply_matches_oracle_under_kernel_round(strategy, spectrum):
    """`atomics.apply` (which now routes through the strategy's lowered
    round) stays bit-identical to the sequential oracle for every layout."""
    n, k, p = 16, 4, 12
    rng = np.random.default_rng(hash((strategy, spectrum)) % 2 ** 31)
    spec = atomics.AtomicSpec(n, k, strategy, p_max=p)
    state = atomics.init(spec)
    ctx = atomics.init_ctx(p, k)
    for _ in range(3):
        d0 = np.asarray(atomics.logical(spec, state))
        v0 = np.asarray(state.version)
        ops, _ = make_batch(rng, n, k, p, spectrum, data=d0,
                            ver=state.version)
        pre_ctx = ctx
        state, ctx, res, stats, _ = atomics.apply(spec, state, ops, ctx)
        rd, rv, rctx, rres = engine.apply_ops_reference(d0, v0, pre_ctx, ops)
        np.testing.assert_array_equal(
            np.asarray(atomics.logical(spec, state)), rd)
        np.testing.assert_array_equal(np.asarray(state.version), rv)
        np.testing.assert_array_equal(np.asarray(res.value), rres.value)
        np.testing.assert_array_equal(np.asarray(res.success), rres.success)
        np.testing.assert_array_equal(np.asarray(ctx.linked), rctx.linked)
        np.testing.assert_array_equal(np.asarray(ctx.version), rctx.version)


def test_pallas_round_via_env_matches_default(monkeypatch):
    """BIGATOMIC_ENGINE_KERNEL=pallas (the CI kernel-exercise matrix) routes
    apply through the interpret-mode kernels and changes nothing — with the
    SAME spec, because the resolved mode rides the jit cache key (a
    mid-process env change must retrace, never reuse the other engine)."""
    n, k, p = 16, 4, 10
    rng = np.random.default_rng(11)
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    ops, _ = make_batch(rng, n, k, p, "low",
                        kinds=[atomics.LOAD, atomics.STORE, atomics.CAS],
                        data=atomics.init(spec).data,
                        ver=atomics.init(spec).version)
    ref_state, _, ref_res, _, _ = atomics.apply(spec, atomics.init(spec),
                                                ops)
    monkeypatch.setenv("BIGATOMIC_ENGINE_KERNEL", "pallas")
    state2, _, res2, _, _ = atomics.apply(spec, atomics.init(spec), ops)
    np.testing.assert_array_equal(np.asarray(ref_res.value),
                                  np.asarray(res2.value))
    np.testing.assert_array_equal(
        np.asarray(atomics.logical(spec, ref_state)),
        np.asarray(atomics.logical(spec, state2)))


# ---------------------------------------------------------------------------
# The fast-path predicate: false positives are impossible.
# ---------------------------------------------------------------------------

def test_predicate_rejects_colliding_writes():
    n, k, p = 16, 2, 8
    kind = np.full(p, atomics.STORE, np.int32)
    slots = np.zeros(p, np.int32)                    # all-same-slot writes
    ops = atomics.make_ops(kind, slots, k=k)
    assert not bool(engine_round.fast_path_ok(n, ops))


def test_predicate_rejects_out_of_range_and_accepts_disjoint():
    n, k = 16, 2
    ops = atomics.make_ops([atomics.LOAD, atomics.STORE],
                           [3, n + 2], k=k)          # active out-of-range
    assert not bool(engine_round.fast_path_ok(n, ops))
    ops = atomics.make_ops([atomics.LOAD, atomics.STORE, atomics.SC],
                           [3, 7, 11], k=k)
    assert bool(engine_round.fast_path_ok(n, ops))


def test_predicate_accepts_read_only_collisions():
    """Reads and validates commute: same-slot LOAD/LL/VALIDATE batches are
    still independent, and the fast round must agree with linearize."""
    n, k, p = 8, 2, 10
    rng = np.random.default_rng(3)
    kind = rng.choice(np.asarray([atomics.LOAD, atomics.LL,
                                  atomics.VALIDATE]), p).astype(np.int32)
    ops = atomics.make_ops(kind, np.zeros(p, np.int32), k=k)
    assert bool(engine_round.fast_path_ok(n, ops))
    data, ver = make_table(n, k, seed=3)
    ctx = atomics.init_ctx(p, k)
    for mode in ("xla", "pallas"):
        round_fn = engine_round.make_round(n, k, mode=mode, interpret=True)
        assert_rounds_equal(engine.linearize(data, ver, ctx, ops),
                            round_fn(data, ver, ctx, ops), mode)


def test_slow_kernel_negative_slot_is_failed_noop():
    """Out-of-contract active slots (here: negative) must never become a
    DMA index: the Pallas slow path treats them as failed no-ops and the
    rest of the batch executes normally."""
    n, k = 8, 2
    data, ver = make_table(n, k, seed=21)
    ctx = atomics.init_ctx(3, k)
    des = np.arange(3 * k, dtype=np.uint32).reshape(3, k) + 1
    ops = atomics.make_ops(
        [atomics.STORE, atomics.STORE, atomics.LOAD], [-1, 3, -5],
        desired=des, k=k)
    assert not bool(engine_round.fast_path_ok(n, ops))
    round_fn = engine_round.make_round(n, k, mode="pallas", interpret=True)
    d2, v2, _, res, _ = round_fn(data, ver, ctx, ops)
    # lane 1 commits; no other row (incl. the would-wrap rows) is touched
    expect = np.asarray(data).copy()
    expect[3] = des[1]
    np.testing.assert_array_equal(np.asarray(d2), expect)
    assert bool(res.success[1])
    assert not bool(res.success[0]) and not bool(res.success[2])
    np.testing.assert_array_equal(np.asarray(res.value[0]), 0)


def test_predicate_never_false_positive_property():
    """Random batches: whenever the predicate says fast, the batch really is
    read-only or duplicate-free among active in-range lanes."""
    n, k, p = 64, 2, 8
    rng = np.random.default_rng(7)
    hits = 0
    for trial in range(200):
        kind = rng.choice(np.asarray(ALL_KINDS), p).astype(np.int32)
        lo, hi = (-2, n + 2) if trial % 2 else (0, n)
        slots = rng.integers(lo, hi, p).astype(np.int32)
        ops = atomics.make_ops(kind, slots, k=k)
        fast = bool(engine_round.fast_path_ok(n, ops))
        active = kind != atomics.IDLE
        writes = active & np.isin(kind, [atomics.STORE, atomics.CAS,
                                         atomics.SC])
        in_range = (slots >= 0) & (slots < n)
        asl = slots[active]
        if fast:
            hits += 1
            assert np.all(in_range[active]), "fast with out-of-range slot"
            assert (not writes.any()) or len(np.unique(asl)) == len(asl), \
                "fast path accepted a colliding batch with writes"
    assert hits > 0                                   # the predicate fires


# ---------------------------------------------------------------------------
# Plug-in fallback: strategies without lower_round stay on linearize.
# ---------------------------------------------------------------------------

def test_plugin_strategy_falls_back_to_linearize():
    class PlainClone(atomics.StrategyImpl):
        name = "engine_round_test_plugin"

    impl = atomics.register_strategy(PlainClone, overwrite=True)
    try:
        assert impl.lower_round(atomics.AtomicSpec(8, 2, impl.name),
                                mode="pallas", interpret=True) is None
        spec = atomics.AtomicSpec(8, 2, impl.name, p_max=8)
        assert engine.round_for(spec) is engine.linearize
        # and the full apply path still matches the oracle
        rng = np.random.default_rng(9)
        state = atomics.init(spec)
        ops, _ = make_batch(rng, 8, 2, 8, "low", data=state.data,
                            ver=state.version)
        d0, v0 = np.asarray(state.data), np.asarray(state.version)
        ctx = atomics.init_ctx(8, 2)
        state2, _, res, _, _ = atomics.apply(spec, state, ops, ctx)
        rd, rv, _, rres = engine.apply_ops_reference(d0, v0, ctx, ops)
        np.testing.assert_array_equal(np.asarray(state2.data), rd)
        np.testing.assert_array_equal(np.asarray(res.success), rres.success)
    finally:
        atomics.unregister_strategy(impl.name)


def test_builtin_strategies_lower_their_round():
    for name in STRATEGIES:
        impl = atomics.get_strategy(name)
        fn = impl.lower_round(atomics.AtomicSpec(8, 2, name), mode="xla",
                              interpret=True)
        assert callable(fn) and fn is not engine.linearize
    for name in ("plain", "simplock"):
        impl = atomics.get_strategy(name)
        assert impl.lower_round(atomics.AtomicSpec(8, 2, name), mode="xla",
                                interpret=True) is None


def test_mode_off_is_pure_linearize(monkeypatch):
    monkeypatch.setenv("BIGATOMIC_ENGINE_KERNEL", "off")
    spec = atomics.AtomicSpec(8, 2, "cached_me", p_max=4)
    assert engine.round_for(spec) is engine.linearize


# ---------------------------------------------------------------------------
# llsc_commit.commit_round is subsumed by the fast-path kernel.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True])
def test_commit_round_subsumed_matches_apply(interpret):
    from repro.kernels.llsc_commit import commit_round

    n, k, p = 8, 4, 6
    rng = np.random.default_rng(13)
    spec = atomics.AtomicSpec(n, k, "seqlock", p_max=p)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    slots = rng.choice(n, p, replace=False).astype(np.int32)

    state = atomics.init(spec, init)
    ctx = atomics.init_ctx(p, k)
    state, ctx, _, _, _ = atomics.apply(
        spec, state, atomics.sync_ops(np.full(p, atomics.LL), slots, k=k),
        ctx)
    desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    # mark lanes 0/3 dead (slot == n), stale-link lane 1 (wrong cell)
    kslots = slots.copy()
    kslots[0] = n
    ctx = ctx._replace(slot=ctx.slot.at[1].set((int(slots[1]) + 1) % n))

    st_k, ctx_k, succ_k, wit_k = commit_round(
        spec, state, ctx, kslots, desired, interpret=interpret)

    kind = np.where(kslots < n, atomics.SC, atomics.IDLE).astype(np.int32)
    st_a, ctx_a, res, _, _ = atomics.apply(
        spec, state, atomics.make_ops(kind, kslots, desired=desired, k=k),
        ctx)
    np.testing.assert_array_equal(np.asarray(atomics.logical(spec, st_k)),
                                  np.asarray(atomics.logical(spec, st_a)))
    np.testing.assert_array_equal(np.asarray(st_k.version),
                                  np.asarray(st_a.version))
    np.testing.assert_array_equal(np.asarray(succ_k),
                                  np.asarray(res.success))
    np.testing.assert_array_equal(np.asarray(wit_k), np.asarray(res.value))
    np.testing.assert_array_equal(np.asarray(ctx_k.linked),
                                  np.asarray(ctx_a.linked))


# ---------------------------------------------------------------------------
# Re-trace hazard (ISSUE 5 satellite): canonicalization + donation.
# ---------------------------------------------------------------------------

def test_apply_does_not_retrace_on_weak_dtypes():
    from repro.analysis import tracing

    n, k, p = 8, 2, 4
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    state = atomics.init(spec)
    slots64 = np.arange(p, dtype=np.int64)           # numpy int64
    slots32 = jnp.arange(p, dtype=jnp.int32)         # committed int32
    ops_a = atomics.OpBatch(
        np.full(p, atomics.LOAD, np.int64), slots64,
        np.zeros((p, k), np.uint32), np.zeros((p, k), np.uint64))
    ops_b = atomics.OpBatch(
        jnp.full((p,), atomics.LOAD, jnp.int32), slots32,
        jnp.zeros((p, k), jnp.uint32), jnp.zeros((p, k), jnp.uint32))
    atomics.apply(spec, state, ops_b)                # establish the trace
    with tracing.assert_max_new_traces(engine._apply, 0):
        atomics.apply(spec, state, ops_a)            # differently typed
        atomics.apply(spec, state, ops_b)


def test_apply_donate_same_results():
    n, k, p = 8, 2, 4
    spec = atomics.AtomicSpec(n, k, "seqlock", p_max=p)
    ops = atomics.stores(np.arange(p), np.ones((p, k), np.uint32), k=k)
    ref, _, _, _, _ = atomics.apply(spec, atomics.init(spec), ops)
    out, _, _, _, _ = atomics.apply(spec, atomics.init(spec), ops,
                                    donate=True)
    np.testing.assert_array_equal(np.asarray(ref.data),
                                  np.asarray(out.data))
    np.testing.assert_array_equal(np.asarray(ref.version),
                                  np.asarray(out.version))
