"""THE shared linearizability harness (ISSUE 3 satellite).

One sequential-replay oracle for every big-atomic surface: given a spec, a
stream of op batches and a CLAIMED linearization order per batch, it replays
the ops one at a time through the repo's defining references
(`engine.apply_ops_reference` for tables, `cachehash.apply_reference` for
hash tables) and diffs the live system's results, values, versions and link
state against the replay.  It replaces the three historical copies of this
logic (tests/test_llsc.py, tests/test_atomics_v2.py and the inline reorder
check in core/distributed.py's v1 `reference_apply`).

Claimed orders: single-node `atomics.apply` linearizes in lane order (the
default); the mesh-sharded layer linearizes in the (owner, src, rank) order
that `distributed.linearization_order` emits, with capacity-rejected lanes
excluded.  Lanes absent from the order are DROPPED: they must have no table
effect and report success=False.
"""

from __future__ import annotations

import numpy as np

from repro import atomics
from repro.core import cachehash as ch
from repro.core import engine


def _np_ctx(ctx) -> engine.LinkCtx:
    return engine.LinkCtx(*[np.array(x, copy=True) for x in ctx])


class TableOracle:
    """Sequential oracle for a k-word big-atomic table + per-lane links."""

    def __init__(self, n: int, k: int, p: int,
                 initial: np.ndarray | None = None):
        self.n, self.k, self.p = n, k, p
        self.data = np.zeros((n, k), np.uint32) if initial is None \
            else np.array(initial, np.uint32)
        self.version = np.zeros((n,), np.uint32)
        self.ctx = engine.LinkCtx(
            np.full((p,), -1, np.int32), np.zeros((p,), np.uint32),
            np.zeros((p, k), np.uint32), np.zeros((p,), bool))

    def step(self, ops: engine.OpBatch, order=None) -> engine.ApplyResult:
        """Replay one batch in the claimed linearization `order` (executed
        lane ids; default = lane order).  Dropped lanes (absent from the
        order) leave no trace and report success=False / zero values.
        Returns the reference ApplyResult in lane order (numpy)."""
        kind = np.asarray(ops.kind)
        slot = np.asarray(ops.slot)
        expected = np.asarray(ops.expected)
        desired = np.asarray(ops.desired)
        if kind.shape[0] != self.p:
            raise ValueError(f"batch width {kind.shape[0]} != p {self.p}")
        order = np.arange(self.p) if order is None \
            else np.asarray(order, np.int64)
        sub = engine.OpBatch(kind[order], slot[order], expected[order],
                             desired[order])
        sub_ctx = engine.LinkCtx(*[np.asarray(x)[order] for x in self.ctx])
        data, ver, nctx, res = engine.apply_ops_reference(
            self.data, self.version, sub_ctx, sub)
        self.data, self.version = data, ver
        merged = _np_ctx(self.ctx)
        for field, rows in zip(engine.LinkCtx._fields, nctx):
            getattr(merged, field)[order] = np.asarray(rows)
        self.ctx = merged
        value = np.zeros((self.p, self.k), np.uint32)
        success = np.zeros((self.p,), bool)
        value[order] = np.asarray(res.value)
        success[order] = np.asarray(res.success)
        return engine.ApplyResult(value, success)

    # -- diffing -------------------------------------------------------------

    def check(self, *, result=None, ref=None, logical=None, version=None,
              ctx=None, overflow=None, msg: str = "") -> None:
        """Diff the live system against the replayed reference.

        result/ref:  live vs reference ApplyResult (values + success);
        logical:     live global logical values (must equal replayed data);
        version:     live global cell versions;
        ctx:         live per-lane LinkCtx;
        overflow:    bool[p] mask of capacity-rejected lanes — these must
                     report success=False (the reported-not-dropped contract).
        """
        if logical is not None:
            np.testing.assert_array_equal(np.asarray(logical), self.data,
                                          err_msg=f"{msg}: logical data")
        if version is not None:
            np.testing.assert_array_equal(np.asarray(version), self.version,
                                          err_msg=f"{msg}: versions")
        if result is not None:
            assert ref is not None, "pass ref= (the value step() returned)"
            np.testing.assert_array_equal(np.asarray(result.value), ref.value,
                                          err_msg=f"{msg}: result values")
            np.testing.assert_array_equal(np.asarray(result.success),
                                          ref.success,
                                          err_msg=f"{msg}: result success")
            if overflow is not None:
                assert not np.asarray(result.success)[overflow].any(), \
                    f"{msg}: overflow lanes must report success=False"
        if ctx is not None:
            for name, live, want in zip(engine.LinkCtx._fields, ctx,
                                        self.ctx):
                np.testing.assert_array_equal(np.asarray(live),
                                              np.asarray(want),
                                              err_msg=f"{msg}: ctx.{name}")

    def step_and_check(self, ops, *, result=None, logical=None, version=None,
                       ctx=None, order=None, overflow=None, msg: str = ""):
        """step() + check() in one call; returns the reference result."""
        ref = self.step(ops, order)
        self.check(result=result, ref=ref, logical=logical, version=version,
                   ctx=ctx, overflow=overflow, msg=msg)
        return ref


class HashOracle:
    """Sequential dict-model oracle for CacheHash FIND/INSERT/DELETE."""

    def __init__(self, vw: int = 1):
        self.vw = vw
        self.model: dict = {}

    def step(self, ops: engine.OpBatch, order=None) -> ch.HashResult:
        kind = np.asarray(ops.kind)
        p = kind.shape[0]
        order = np.arange(p) if order is None else np.asarray(order, np.int64)
        sub = engine.OpBatch(
            kind[order], np.asarray(ops.slot)[order],
            np.asarray(ops.expected)[order], np.asarray(ops.desired)[order])
        self.model, res = ch.apply_reference(self.model, sub, self.vw)
        found = np.zeros((p,), bool)
        value = np.zeros((p, self.vw), np.uint32)
        found[order] = np.asarray(res.found)
        value[order] = np.asarray(res.value)
        return ch.HashResult(found, value, np.zeros((p,), bool))

    def check(self, *, result=None, ref=None, items=None, overflow=None,
              msg: str = "") -> None:
        if result is not None:
            assert ref is not None
            np.testing.assert_array_equal(np.asarray(result.found), ref.found,
                                          err_msg=f"{msg}: found")
            np.testing.assert_array_equal(np.asarray(result.value), ref.value,
                                          err_msg=f"{msg}: values")
            if overflow is not None:
                assert not np.asarray(result.found)[overflow].any(), \
                    f"{msg}: overflow lanes must report found=False"
        if items is not None:
            want = {k: list(np.ravel(v)) for k, v in self.model.items()}
            got = {k: list(np.ravel(v)) for k, v in items.items()}
            assert got == want, f"{msg}: table contents diverge"

    def step_and_check(self, ops, *, result=None, items=None, order=None,
                       overflow=None, msg: str = ""):
        ref = self.step(ops, order)
        self.check(result=result, ref=ref, items=items, overflow=overflow,
                   msg=msg)
        return ref


class TxnOracle:
    """Sequential whole-transaction oracle for k-word MCAS (ISSUE 4).

    Replays CLAIMED linearization orders of entire transactions — each one
    all-or-nothing, including aborted txns (which must leave no trace but
    still witness a consistent read of every claimed cell) — through
    `txn.mcas.mcas_reference`, and diffs the live system's success masks,
    witnesses, logical values and versions against the replay."""

    def __init__(self, n: int, k: int, initial: np.ndarray | None = None):
        self.n, self.k = n, k
        self.data = np.zeros((n, k), np.uint32) if initial is None \
            else np.array(initial, np.uint32)
        self.version = np.zeros((n,), np.uint32)

    def step(self, txns, order=None):
        """Replay one txn batch in the claimed `order` (default: txn id
        order).  Returns (success[T], witness[T, W, k]) as numpy."""
        from repro.txn import mcas as txn_mcas
        if order is None:
            order = np.arange(np.asarray(txns.slot).shape[0])
        self.data, self.version, success, witness = \
            txn_mcas.mcas_reference(self.data, self.version, txns, order)
        return success, witness

    def check(self, *, result=None, ref=None, logical=None, version=None,
              msg: str = "") -> None:
        if logical is not None:
            np.testing.assert_array_equal(np.asarray(logical), self.data,
                                          err_msg=f"{msg}: logical data")
        if version is not None:
            np.testing.assert_array_equal(np.asarray(version), self.version,
                                          err_msg=f"{msg}: versions")
        if result is not None:
            assert ref is not None, "pass ref= (the value step() returned)"
            ref_success, ref_witness = ref
            np.testing.assert_array_equal(np.asarray(result.success),
                                          ref_success,
                                          err_msg=f"{msg}: txn success")
            np.testing.assert_array_equal(np.asarray(result.witness),
                                          ref_witness,
                                          err_msg=f"{msg}: txn witness")

    def step_and_check(self, txns, *, result=None, logical=None,
                       version=None, order=None, msg: str = ""):
        """step() + check() in one call; `order` defaults to the claimed
        order the live result encodes.  Returns the reference tuple."""
        from repro.txn import mcas as txn_mcas
        if order is None and result is not None:
            order = txn_mcas.linearization_order(result)
        ref = self.step(txns, order)
        self.check(result=result, ref=ref, logical=logical, version=version,
                   msg=msg)
        return ref


class MapOracle:
    """Sequential dict-model oracle for the transactional map: replays
    whole read-set/write-set transactions in the claimed serialization."""

    def __init__(self, vw: int = 1):
        self.vw = vw
        self.model: dict = {}

    def step(self, txns, fn, order=None):
        from repro.txn import map as txn_map
        if order is None:
            order = np.arange(txns.t)
        self.model, rv, rf = txn_map.transact_reference(
            self.model, txns, fn, order, self.vw)
        return rv, rf

    def check(self, *, result=None, ref=None, items=None,
              msg: str = "") -> None:
        if result is not None:
            assert ref is not None
            rv, rf = ref
            np.testing.assert_array_equal(np.asarray(result.read_found), rf,
                                          err_msg=f"{msg}: read_found")
            np.testing.assert_array_equal(np.asarray(result.read_value), rv,
                                          err_msg=f"{msg}: read_value")
        if items is not None:
            want = {k: list(np.ravel(v)) for k, v in self.model.items()}
            got = {k: list(np.ravel(v)) for k, v in items.items()}
            assert got == want, f"{msg}: table contents diverge"

    def step_and_check(self, txns, fn, *, result=None, items=None,
                       order=None, msg: str = ""):
        from repro.txn import map as txn_map
        if order is None and result is not None:
            order = txn_map.linearization_order(result)
        ref = self.step(txns, fn, order)
        self.check(result=result, ref=ref, items=items, msg=msg)
        return ref


# ---------------------------------------------------------------------------
# Executor histories: the multi-stream interleaving as ONE linearization.
# ---------------------------------------------------------------------------

def replay_executor_history(n: int, k: int, widths: list[int], history, *,
                            initial=None, check: bool = True) -> TableOracle:
    """Replay a `runtime.Executor` issue history — S streams' batches in
    their issue interleaving, each with its claimed per-batch order —
    through ONE sequential TableOracle, and diff every delivered result.

    Each stream owns a fixed lane slice of a width-sum(widths) oracle
    (stream si's lane j is oracle lane offset(si) + j), so per-stream
    LL/SC link state persists across batches exactly as the executor's
    per-stream LinkCtx does.  Works unchanged across a recovery boundary:
    post-recovery records carry orders computed under the NEW geometry,
    and replayed (re-delivered) seqs simply appear as fresh records whose
    results must STILL match — that is the linearizability-across-the-
    fault claim being checked.

    history: iterable of `runtime.executor.IssueRec` (retired, i.e. with
    value/success filled).  Returns the oracle (final data/versions inside)
    for end-state diffs against the live target.
    """
    offs = np.concatenate([[0], np.cumsum(widths)]).astype(np.int64)
    p_all = int(offs[-1])
    oracle = TableOracle(n, k, p_all, initial=initial)
    for rec in history:
        si, off, w = rec.stream, int(offs[rec.stream]), widths[rec.stream]
        kind = np.asarray(rec.ops.kind)
        q = kind.shape[0]
        assert q <= w, f"stream {si} batch width {q} > declared {w}"
        pk = np.full(p_all, engine.IDLE, np.int32)
        ps = np.zeros(p_all, np.int32)
        pe = np.zeros((p_all, k), np.uint32)
        pd = np.zeros((p_all, k), np.uint32)
        pk[off:off + q] = kind
        ps[off:off + q] = np.asarray(rec.ops.slot)
        pe[off:off + q] = np.asarray(rec.ops.expected)
        pd[off:off + q] = np.asarray(rec.ops.desired)
        order = (np.arange(q, dtype=np.int64) if rec.order is None
                 else np.asarray(rec.order, np.int64)) + off
        ref = oracle.step(engine.OpBatch(pk, ps, pe, pd), order=order)
        if not check:
            continue
        msg = f"stream {si} seq {rec.seq}"
        np.testing.assert_array_equal(
            rec.value, ref.value[off:off + q], err_msg=f"{msg}: values")
        np.testing.assert_array_equal(
            rec.success, ref.success[off:off + q], err_msg=f"{msg}: success")
        if rec.overflow is not None:
            assert not np.asarray(rec.success)[rec.overflow].any(), \
                f"{msg}: overflow lanes must report success=False"
    return oracle


# ---------------------------------------------------------------------------
# Telemetry recount (ISSUE 9): the repro.obs counters, recomputed in numpy
# from claimed linearization orders / delivered results alone.
# ---------------------------------------------------------------------------

def _np_fast_path_ok(n: int, kind: np.ndarray, slot: np.ndarray) -> bool:
    """Numpy mirror of `kernels.engine_round.fast_path_ok`."""
    active = kind != engine.IDLE
    in_range = (slot >= 0) & (slot < n)
    all_in = not np.any(active & ~in_range)
    is_write = active & ((kind == engine.STORE) | (kind == engine.CAS)
                         | (kind == engine.SC))
    read_only = not np.any(is_write)
    cslot = np.where(active & in_range, slot, n).astype(np.int64)
    counts = np.bincount(cslot, minlength=n + 1)
    no_dup = np.max(counts[:n], initial=0) <= 1
    return bool(all_in and (read_only or no_dup))


def _np_contention_hist(n: int, kind: np.ndarray, slot: np.ndarray):
    """Numpy mirror of the telemetry contention histogram: cells bucketed by
    floor(log2(active lanes)) via the SAME integer-threshold compares as the
    in-graph version (`obs.telemetry.contention_bucket`) — bit-exact."""
    from repro.obs.telemetry import N_HIST
    active = kind != engine.IDLE
    in_range = (slot >= 0) & (slot < n)
    cslot = np.where(active & in_range, slot, n).astype(np.int64)
    c = np.bincount(cslot, minlength=n + 1)[:n]
    c = c[c > 0]
    th = 2 ** np.arange(1, N_HIST, dtype=np.int64)
    bucket = (c[:, None] >= th[None, :]).sum(axis=1)
    return np.bincount(bucket, minlength=N_HIST).astype(np.int64)


def _np_stats_sorted(n: int, kind: np.ndarray, slot: np.ndarray,
                     success: np.ndarray):
    """Numpy mirror of `engine.stats_on_sorted` on the (slot, lane)-sorted
    order, fed the DELIVERED per-lane success (within the engine contract
    `result.success` equals the internal sorted-order update success on
    every STORE/CAS/SC lane, which is the only place it is read).
    Returns (rounds, n_raced_loads, n_dirty_cells)."""
    p = kind.shape[0]
    active = kind != engine.IDLE
    aslot = np.where(active, slot, n)
    order = np.argsort(aslot, kind="stable")
    s_slot, s_kind, succ_s = aslot[order], kind[order], success[order]
    seg_start = np.ones(p, bool)
    seg_start[1:] = s_slot[1:] != s_slot[:-1]
    seg_id = np.cumsum(seg_start) - 1
    is_valcas = (s_kind == engine.STORE) | (s_kind == engine.CAS)
    is_sc = (s_kind == engine.SC) & (s_slot < n)
    is_upd = is_valcas | is_sc
    is_read = (s_kind == engine.LOAD) | (s_kind == engine.LL)
    excl_upd = np.cumsum(is_upd) - is_upd
    start_idx = np.arange(p)[seg_start][seg_id]
    upd_rank = excl_upd - excl_upd[start_idx]
    n_rounds = int(upd_rank[is_upd].max() + 1) if is_upd.any() else 0
    rounds = n_rounds if is_valcas.any() else (1 if is_sc.any() else 0)
    wrote = is_valcas | (is_sc & succ_s)
    # `engine._seg_broadcast_any` is a flipped inclusive scan: a SUFFIX-any
    # within the segment (any(flags[i:seg_last])), so a load only races a
    # write AT-OR-AFTER it in sorted order.  Mirror that exactly; for
    # `dirty` (read at seg starts only) suffix-any == whole-segment any.
    def _suffix_any(flags):
        out = np.zeros(p, bool)
        acc = False
        for i in range(p - 1, -1, -1):
            if i == p - 1 or seg_start[i + 1]:
                acc = False
            acc = acc or bool(flags[i])
            out[i] = acc
        return out

    raced = int(np.sum(is_read & _suffix_any(wrote)))
    dirty = int(np.sum(seg_start & _suffix_any(succ_s & is_upd)
                       & (s_slot < n)))
    return rounds, raced, dirty


def _np_windows(n, ops):
    """Distinct live windows of a batch: 128-cell windows for k < 128,
    cells for k >= 128 (the slow kernel's window round trips)."""
    kind, slot = np.asarray(ops.kind), np.asarray(ops.slot)
    live = (kind != engine.IDLE) & (slot >= 0) & (slot < n)
    per = 128 if np.asarray(ops.expected).shape[1] < 128 else 1
    return len(np.unique(slot[live] // per))


class TelemetryOracle:
    """Recount the `repro.obs` in-graph counters from the oracle's own
    inputs: op batches, delivered results, MCAS results and distributed
    claimed orders.  `tests/test_obs.py` requires `counts()` to equal the
    matching keys of `obs.snapshot()` BIT-EXACTLY across strategies and
    engine-kernel modes — the counters are definitions, not estimates."""

    _KINDS = ("load", "store", "cas", "idle", "ll", "sc", "validate",
              "find", "insert", "delete")

    def __init__(self, n: int):
        from repro.obs.telemetry import N_HIST
        self.n = n
        self._n_hist = N_HIST
        self.c: dict[str, int] = {}

    def _add(self, name: str, v) -> None:
        self.c[name] = self.c.get(name, 0) + int(v)

    def count_table_batch(self, ops, result, *, fused: bool) -> None:
        """One `engine.apply` batch: `fused` says whether the engine ran a
        lowered kernel round (resolved BIGATOMIC_ENGINE_KERNEL != off)."""
        kind = np.asarray(ops.kind)
        slot = np.asarray(ops.slot)
        success = np.asarray(result.success)
        active = kind != engine.IDLE
        self._add("engine.batches", 1)
        for j, name in enumerate(self._KINDS):
            self._add(f"engine.ops.{name}", np.sum(kind == j))
        eligible = _np_fast_path_ok(self.n, kind, slot)
        taken = eligible and fused
        self._add("engine.fast.eligible", eligible)
        self._add("engine.fast.taken", taken)
        rounds, raced, dirty = _np_stats_sorted(self.n, kind, slot, success)
        self._add("engine.rounds.total", rounds)
        self._add("engine.rounds.slow", 0 if taken else rounds)
        self._add("engine.slow.windows",
                  0 if taken else _np_windows(self.n, ops))
        self._add("engine.fail.cas",
                  np.sum(active & (kind == engine.CAS) & ~success))
        self._add("engine.fail.sc",
                  np.sum(active & (kind == engine.SC) & ~success))
        self._add("engine.loads.raced", raced)
        self._add("engine.cells.dirty", dirty)
        hist = _np_contention_hist(self.n, kind, slot)
        for b in range(self._n_hist):
            self._add(f"engine.contention.log2_{b:02d}", hist[b])

    def count_read(self, ok) -> None:
        self._add("read.torn_retries", np.sum(~np.asarray(ok)))

    def count_mcas(self, result) -> None:
        """One drained `txn.mcas` run, recounted from the McasResult alone:
        every resolved txn committed or aborted in exactly one round, and
        `attempts` journals each arbitration loss (= backoff event)."""
        success = np.asarray(result.success)
        rnd = np.asarray(result.round)
        self._add("mcas.commits", np.sum(success))
        self._add("mcas.aborts", np.sum((rnd > 0) & ~success))
        self._add("mcas.rounds", int(result.rounds))
        self._add("mcas.backoff", np.sum(np.asarray(result.attempts)))

    def count_dist_batch(self, overflow, words: int) -> None:
        """One `distributed.apply` collective round, from the claimed-order
        overflow mask (`distributed.linearization_order`) and the static
        `distributed.collective_words(dspec)`."""
        self._add("dist.route_overflow", np.sum(np.asarray(overflow)))
        self._add("dist.rounds", 1)
        self._add("dist.words", words)

    def counts(self) -> dict:
        """Every recounted metric, keyed exactly like `obs.snapshot()`."""
        return dict(self.c)


# ---------------------------------------------------------------------------
# Shared randomized batch generators (tests + the distributed suite).
# ---------------------------------------------------------------------------

def mixed_batch(rng: np.random.Generator, ref_ctx, *, p: int, n: int, k: int,
                current: np.ndarray) -> engine.OpBatch:
    """All seven table kinds in one batch; SC/VALIDATE lanes mostly target
    their live link, half the CAS comparands match the live value."""
    kind = rng.integers(0, 7, p).astype(np.int32)
    slot = rng.integers(0, n, p).astype(np.int32)
    linked = np.asarray(ref_ctx.linked)
    lslot = np.asarray(ref_ctx.slot)
    for i in range(p):
        if kind[i] in (atomics.SC, atomics.VALIDATE) and linked[i] \
                and rng.random() < 0.7:
            slot[i] = lslot[i]
    expected = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    use_cur = rng.random(p) < 0.5
    expected = np.where(use_cur[:, None], np.asarray(current)[slot], expected)
    desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    return atomics.make_ops(kind, slot, expected, desired, k=k)


def txn_batch(rng: np.random.Generator, *, t: int, w: int, n: int, k: int,
              current: np.ndarray, match_frac: float = 0.6):
    """Random MCAS batch: mixed widths (-1-padded lanes), distinct slots
    per txn, `match_frac` of txns expecting the CURRENT values (commit
    candidates; small n => real conflicts), the rest stale comparands."""
    slot = np.full((t, w), -1, np.int32)
    for i in range(t):
        width = int(rng.integers(1, w + 1))
        slot[i, :width] = rng.choice(n, size=min(width, n), replace=False)
    expected = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    fresh = rng.random(t) < match_frac
    for i in range(t):
        if fresh[i]:
            for j in range(w):
                if slot[i, j] >= 0:
                    expected[i, j] = np.asarray(current)[slot[i, j]]
    desired = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    return atomics.make_txns(slot, expected, desired, k=k)


def hash_batch(rng: np.random.Generator, *, p: int, key_space: int,
               vw: int = 1) -> engine.OpBatch:
    """Random FIND/INSERT/DELETE batch over a bounded key space."""
    kind = rng.integers(atomics.FIND, atomics.DELETE + 1, p).astype(np.int32)
    keys = rng.integers(0, key_space, p).astype(np.uint32)
    vals = rng.integers(0, 2 ** 32, (p, vw), dtype=np.uint32)
    return ch.make_hash_ops(kind, keys, vals, vw=vw)
