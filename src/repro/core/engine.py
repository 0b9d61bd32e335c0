"""The unified big-atomic engine: ONE op schema, ONE linearization.

This module merges the repo's three historical op-batch schemas
(`core/semantics.OpBatch` for load/store/CAS, `sync/llsc.SyncOpBatch` for
LL/SC/validate, `core/cachehash.OpBatch` for hash ops) into a single
`OpBatch` whose per-lane `kind` covers

    LOAD / STORE / CAS / IDLE        (value ops, numeric-compatible with v1)
    LL / SC / VALIDATE               (version ops, per-lane LinkCtx)
    FIND / INSERT / DELETE           (hash ops, dispatched by cachehash)

and gives the first seven ONE vectorized linearization, `linearize`, that is
bit-identical to the sequential oracle `apply_ops_reference`: ops apply in
lane order; STORE/CAS serialize within a cell segment (L combining rounds);
SC commits iff its lane's link version still matches the cell.  Mixed
batches — a decode lookup, a page CAS, and a queue SC in the same round —
therefore linearize in one call.

Fast path: when a batch carries no STORE/CAS lanes, the one-SC-per-cell-
per-batch fact (DESIGN.md §4) applies — every link predates the batch, so
the first eligible SC per cell wins and everyone behind it is stale.  The
engine detects this at runtime (`lax.cond`) and resolves the whole batch in
closed form, ONE round, instead of the L-round combining loop.

`apply(spec, state, ops, ctx)` is the single table-level entry point: `spec`
(an `AtomicSpec`) is the only static argument; layout maintenance and the
traffic model dispatch through the strategy registry, so new layouts plug in
without touching this file.

Execution is two-tier since ISSUE 5 (DESIGN.md §8): `linearize` below is the
pure-XLA *reference* executor, and `round_for(spec)` swaps in the strategy's
lowered fused round (`repro.kernels.engine_round` — a runtime fast path for
collision-free batches, a single-pass sequential-replay kernel for contended
ones) whenever the layout provides one.  Every result remains bit-identical
to `linearize`, which remains bit-identical to `apply_ops_reference`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import registry
from repro.core.layout import WORD_DTYPE
from repro.core.specs import AtomicSpec
from repro.obs import telemetry as obs_telemetry

# Op kinds.  LOAD/STORE/CAS/IDLE keep their v1 numeric values so legacy
# `semantics.OpBatch` instances are valid unified batches as-is.
LOAD = 0
STORE = 1
CAS = 2
IDLE = 3     # padding lane: reads slot 0, reports invalid
LL = 4       # load-linked: read value, link (slot, version)
SC = 5       # store-conditional: commit desired iff link still valid
VALIDATE = 6  # is my link still valid?  (never writes)

# Hash-table kinds (same schema, dispatched by cachehash.apply_hash; the
# `slot` field carries the uint32 key bit-pattern, `desired[:, :vw]` the
# value).  Kept in one namespace so a kind value means one thing everywhere.
FIND = 7
INSERT = 8
DELETE = 9

TABLE_KINDS = (LOAD, STORE, CAS, IDLE, LL, SC, VALIDATE)
HASH_KINDS = (FIND, INSERT, DELETE, IDLE)


class OpBatch(NamedTuple):
    """A batch of `p` operations over an `(n, k)` table.

    kind:     int32[p]   — one of the kind constants above
    slot:     int32[p]   — target cell index in [0, n)  (hash ops: key bits)
    expected: word[p, k] — CAS comparand (ignored otherwise)
    desired:  word[p, k] — value to write (STORE / successful CAS / SC;
                           hash ops: INSERT value in the first vw words)
    """

    kind: jax.Array
    slot: jax.Array
    expected: jax.Array
    desired: jax.Array

    @property
    def p(self) -> int:
        return self.kind.shape[0]

    @property
    def k(self) -> int:
        return self.desired.shape[1]


class LinkCtx(NamedTuple):
    """Per-lane link state, carried across batches (a pure pytree).

    slot:    int32[p]   linked cell (-1 = never linked)
    version: uint32[p]  version observed at the LL
    value:   word[p,k]  value observed at the LL
    linked:  bool[p]    link is live (consumed by any SC attempt)
    """

    slot: jax.Array
    version: jax.Array
    value: jax.Array
    linked: jax.Array


class ApplyResult(NamedTuple):
    """Per-lane results of a linearized batch.

    value:   word[p, k] — the value witnessed at the op's linearization point
                          (loads/LL: the value read; CAS/SC: the pre-value).
    success: bool[p]    — CAS/SC success, VALIDATE link validity
                          (LOAD/STORE/LL: True, IDLE: False).
    """

    value: jax.Array
    success: jax.Array


class ApplyStats(NamedTuple):
    """Traffic/contention statistics for one batch (all scalars).

    rounds:        serialization rounds L (1 on the pure-sync fast path).
    n_updates:     store/CAS lanes + successful SC lanes (writes attempted).
    n_loads:       LOAD + LL lanes.
    n_cas_fail:    CAS/SC lanes that failed.
    n_raced_loads: loads whose cell had >=1 write in this batch (these take
                   the slow path in the cached strategies).
    n_dirty_cells: distinct cells receiving >=1 successful write.
    """

    rounds: jax.Array
    n_updates: jax.Array
    n_loads: jax.Array
    n_cas_fail: jax.Array
    n_raced_loads: jax.Array
    n_dirty_cells: jax.Array


def init_ctx(p: int, k: int) -> LinkCtx:
    return LinkCtx(
        slot=jnp.full((p,), -1, jnp.int32),
        version=jnp.zeros((p,), jnp.uint32),
        value=jnp.zeros((p, k), WORD_DTYPE),
        linked=jnp.zeros((p,), bool),
    )


def make_ops(kind, slot, expected=None, desired=None, *, k: int) -> OpBatch:
    """THE checked op-batch constructor: every public wrapper routes through
    here so validation and dtype coercion can never be skipped.

    Checks (on concrete inputs): kind values are known, shapes line up with
    the batch width p and cell width k.  Word payloads are coerced to the
    canonical WORD_DTYPE (uint32)."""
    kind = jnp.asarray(kind, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    if kind.ndim != 1:
        raise ValueError(f"kind must be rank-1, got shape {kind.shape}")
    p = kind.shape[0]
    if slot.shape != (p,):
        raise ValueError(f"slot shape {slot.shape} != ({p},)")
    try:
        kind_np = np.asarray(kind)          # concrete only; tracers skip
    except jax.errors.TracerArrayConversionError:
        kind_np = None
    if kind_np is not None:
        bad = np.setdiff1d(kind_np, np.arange(DELETE + 1))
        if bad.size:
            raise ValueError(f"unknown op kinds {bad.tolist()}")
    if expected is None:
        expected = jnp.zeros((p, k), WORD_DTYPE)
    else:
        expected = jnp.asarray(expected, WORD_DTYPE)
    if desired is None:
        desired = jnp.zeros((p, k), WORD_DTYPE)
    else:
        desired = jnp.asarray(desired, WORD_DTYPE)
    for name, arr in (("expected", expected), ("desired", desired)):
        if arr.shape != (p, k):
            raise ValueError(f"{name} shape {arr.shape} != ({p}, {k})")
    return OpBatch(kind, slot, expected, desired)


def loads(slots, *, k: int) -> OpBatch:
    slots = jnp.asarray(slots, jnp.int32)
    return make_ops(jnp.full(slots.shape, LOAD, jnp.int32), slots, k=k)


def stores(slots, desired, *, k: int) -> OpBatch:
    slots = jnp.asarray(slots, jnp.int32)
    return make_ops(jnp.full(slots.shape, STORE, jnp.int32), slots,
                    desired=desired, k=k)


def cas_ops(slots, expected, desired, *, k: int) -> OpBatch:
    slots = jnp.asarray(slots, jnp.int32)
    return make_ops(jnp.full(slots.shape, CAS, jnp.int32), slots,
                    expected=expected, desired=desired, k=k)


def sync_ops(kind, slots, desired=None, *, k: int) -> OpBatch:
    return make_ops(kind, slots, desired=desired, k=k)


# ---------------------------------------------------------------------------
# Sequential oracle (numpy) — THE definition of correctness.
# ---------------------------------------------------------------------------

def apply_ops_reference(data: np.ndarray, version: np.ndarray,
                        ctx: LinkCtx, ops: OpBatch):
    """Apply mixed table ops one at a time in lane order.  Pure numpy.

    Returns (new_data, new_version, new_ctx, ApplyResult-as-numpy)."""
    data = np.array(data, copy=True)
    version = np.array(version, copy=True)
    c_slot = np.array(ctx.slot, copy=True)
    c_ver = np.array(ctx.version, copy=True)
    c_val = np.array(ctx.value, copy=True)
    c_lnk = np.array(ctx.linked, copy=True)
    kind = np.asarray(ops.kind)
    slot = np.asarray(ops.slot)
    expected = np.asarray(ops.expected)
    desired = np.asarray(ops.desired)
    p, k = desired.shape
    value = np.zeros((p, k), dtype=data.dtype)
    success = np.zeros((p,), dtype=bool)
    for i in range(p):
        s = slot[i]
        if kind[i] == IDLE:
            continue
        cur = data[s].copy()
        value[i] = cur
        if kind[i] == LOAD:
            success[i] = True
        elif kind[i] == STORE:
            data[s] = desired[i]
            version[s] += 2
            success[i] = True
        elif kind[i] == CAS:
            if np.array_equal(cur, expected[i]):
                data[s] = desired[i]
                version[s] += 2
                success[i] = True
        elif kind[i] == LL:
            c_slot[i], c_ver[i], c_val[i], c_lnk[i] = \
                s, version[s], cur, True
            success[i] = True
        elif kind[i] == VALIDATE:
            success[i] = bool(c_lnk[i] and c_slot[i] == s
                              and c_ver[i] == version[s])
        elif kind[i] == SC:
            ok = bool(c_lnk[i] and c_slot[i] == s
                      and c_ver[i] == version[s])
            if ok:
                data[s] = desired[i]
                version[s] += 2
            c_lnk[i] = False            # any SC attempt consumes the link
            success[i] = ok
        else:
            raise ValueError(f"lane {i}: kind {kind[i]} is not a table op")
    new_ctx = LinkCtx(c_slot, c_ver, c_val, c_lnk)
    return data, version, new_ctx, ApplyResult(value, success)


# ---------------------------------------------------------------------------
# Vectorized linearization (jnp) — bit-identical to the oracle.
# ---------------------------------------------------------------------------

def _segmented_scan_max(values: jax.Array, seg_start: jax.Array) -> jax.Array:
    """Inclusive segmented max-scan.  seg_start marks first element of a segment."""

    def combine(a, b):
        a_flag, a_val = a
        b_flag, b_val = b
        val = jnp.where(b_flag, b_val, jnp.maximum(a_val, b_val))
        return (a_flag | b_flag, val)

    _, out = lax.associative_scan(combine, (seg_start, values))
    return out


def _seg_broadcast_any(flags: jax.Array, seg_end: jax.Array) -> jax.Array:
    """Broadcast `any(flags)` within each segment to all its members."""
    rev = _segmented_scan_max(jnp.flip(flags.astype(jnp.int32)),
                              jnp.flip(seg_end))
    return jnp.flip(rev) > 0


def stats_on_sorted(n: int, s_slot, s_kind, succ_s) -> ApplyStats:
    """`ApplyStats` from the (slot, lane)-sorted order — THE single
    definition, shared by `linearize` and the fused kernel round
    (`repro.kernels.engine_round`), so the two can never drift.

    succ_s is per-lane update success in sorted order (meaningful for
    STORE/CAS/SC lanes; the closed `rounds` form below equals what the
    general/pure-sync execution branches would report)."""
    p = s_slot.shape[0]
    idx = jnp.arange(p, dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), s_slot[1:] != s_slot[:-1]])
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones((1,), bool)])
    start_idx = _segmented_scan_max(jnp.where(seg_start, idx, -1), seg_start)
    is_valcas = (s_kind == STORE) | (s_kind == CAS)
    is_sc = (s_kind == SC) & (s_slot < n)
    is_upd = is_valcas | is_sc
    is_read = (s_kind == LOAD) | (s_kind == LL)
    cum_upd = jnp.cumsum(is_upd.astype(jnp.int32))
    excl_upd = cum_upd - is_upd.astype(jnp.int32)
    upd_rank = excl_upd - excl_upd[start_idx]
    n_rounds = jnp.where(jnp.any(is_upd),
                         jnp.max(jnp.where(is_upd, upd_rank, -1)) + 1, 0)
    wrote = is_valcas | (is_sc & succ_s)
    seg_any_wrote = _seg_broadcast_any(wrote, seg_end)
    seg_any_succ = _seg_broadcast_any(succ_s & is_upd, seg_end)
    return ApplyStats(
        rounds=jnp.where(jnp.any(is_valcas), n_rounds,
                         jnp.where(jnp.any(is_sc), 1, 0)).astype(jnp.int32),
        n_updates=jnp.sum(wrote.astype(jnp.int32)),
        n_loads=jnp.sum(is_read.astype(jnp.int32)),
        n_cas_fail=jnp.sum((((s_kind == CAS) | is_sc) & ~succ_s)
                           .astype(jnp.int32)),
        n_raced_loads=jnp.sum((is_read & seg_any_wrote).astype(jnp.int32)),
        n_dirty_cells=jnp.sum((seg_start & seg_any_succ & (s_slot < n))
                              .astype(jnp.int32)),
    )


@jax.jit
def linearize(data: jax.Array, version: jax.Array, ctx: LinkCtx,
              ops: OpBatch):
    """Linearize a mixed LOAD/STORE/CAS/LL/SC/VALIDATE batch in lane order.

    Returns (data', version', ctx', ApplyResult, ApplyStats).  `data` is
    word[n, k]; `version` is uint32[n] (bumped by 2 per successful write,
    paper-style even==unlocked parity)."""
    n, k = data.shape
    p = ops.p
    kind = ops.kind

    active = kind != IDLE
    # Inactive lanes get an out-of-range slot so they can never collide.
    slot = jnp.where(active, ops.slot, n)

    order = jnp.argsort(slot, stable=True)  # (slot, lane) lexicographic
    inv = jnp.argsort(order, stable=True)

    s_slot = slot[order]
    s_kind = kind[order]
    s_expected = ops.expected[order]
    s_desired = ops.desired[order]
    s_cslot = ctx.slot[order]
    s_cver = ctx.version[order]
    s_clnk = ctx.linked[order]

    idx = jnp.arange(p, dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), s_slot[1:] != s_slot[:-1]])
    start_idx = _segmented_scan_max(jnp.where(seg_start, idx, -1), seg_start)

    is_valcas = (s_kind == STORE) | (s_kind == CAS)
    is_sc = (s_kind == SC) & (s_slot < n)
    is_upd = is_valcas | is_sc
    # Exclusive count of updates before each position, segment-scoped.
    cum_upd = jnp.cumsum(is_upd.astype(jnp.int32))
    excl_upd = cum_upd - is_upd.astype(jnp.int32)
    upd_rank = excl_upd - excl_upd[start_idx]
    n_rounds = jnp.where(jnp.any(is_upd),
                         jnp.max(jnp.where(is_upd, upd_rank, -1)) + 1, 0)

    safe_slot = jnp.minimum(s_slot, n - 1)
    init_vals = data[safe_slot]          # pre-batch values per lane
    ver0 = version[safe_slot]            # pre-batch versions per lane

    def _general(data, version):
        """L-round combining loop: round t applies the t-th write of every
        cell segment in parallel (masked gather -> check -> masked scatter).
        Handles arbitrary STORE/CAS/SC interleavings."""
        res_after = jnp.zeros((p, k), data.dtype)   # value AFTER each write lane
        ver_after = jnp.zeros((p,), jnp.uint32)     # version AFTER each write lane
        witness = jnp.zeros((p, k), data.dtype)     # value BEFORE each write lane
        wver = jnp.zeros((p,), jnp.uint32)          # version BEFORE each write lane
        succ = jnp.zeros((p,), bool)

        def body(state):
            t, data_, version_, res_after_, ver_after_, witness_, wver_, succ_ = state
            live = is_upd & (upd_rank == t)
            cur = data_[safe_slot]
            curv = version_[safe_slot]
            match = jnp.all(cur == s_expected, axis=1)
            link_ok = s_clnk & (s_cslot == s_slot) & (s_cver == curv)
            ok = live & jnp.where(
                s_kind == STORE, True,
                jnp.where(s_kind == CAS, match, link_ok))
            w_idx = jnp.where(ok, s_slot, n)        # masked scatter (drop)
            data_ = data_.at[w_idx].set(s_desired, mode="drop")
            version_ = version_.at[w_idx].add(jnp.uint32(2), mode="drop")
            res_after_ = jnp.where(live[:, None],
                                   jnp.where(ok[:, None], s_desired, cur),
                                   res_after_)
            ver_after_ = jnp.where(live, curv + 2 * ok.astype(jnp.uint32),
                                   ver_after_)
            witness_ = jnp.where(live[:, None], cur, witness_)
            wver_ = jnp.where(live, curv, wver_)
            succ_ = jnp.where(live, ok, succ_)
            return (t + 1, data_, version_, res_after_, ver_after_,
                    witness_, wver_, succ_)

        out = lax.while_loop(
            lambda st: st[0] < n_rounds, body,
            (jnp.int32(0), data, version, res_after, ver_after,
             witness, wver, succ))
        _, data, version, res_after, ver_after, witness, wver, succ = out

        # Non-write lanes observe the last write preceding them in-segment.
        upd_pos = jnp.where(is_upd, idx, -1)
        prev_upd = _segmented_scan_max(upd_pos, seg_start)
        has_prev = prev_upd >= 0
        val_pt = jnp.where(has_prev[:, None],
                           res_after[jnp.maximum(prev_upd, 0)], init_vals)
        ver_pt = jnp.where(has_prev, ver_after[jnp.maximum(prev_upd, 0)],
                           ver0)
        val_s = jnp.where(is_upd[:, None], witness, val_pt)
        verpt_s = jnp.where(is_upd, wver, ver_pt)
        return data, version, val_s, verpt_s, succ

    def _fast(data, version):
        """One-round closed form for batches without STORE/CAS lanes: every
        SC's link predates the batch, so the first eligible SC per cell wins
        and every later SC on that cell is already stale (DESIGN.md §4)."""
        eligible = is_sc & s_clnk & (s_cslot == s_slot) & (s_cver == ver0)
        elig_incl = _segmented_scan_max(eligible.astype(jnp.int32), seg_start)
        elig_before = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), elig_incl[:-1]])
        elig_before = jnp.where(seg_start, 0, elig_before) > 0
        win = eligible & ~elig_before
        # Lanes strictly after the winner observe the committed value/version.
        wpos_incl = _segmented_scan_max(jnp.where(win, idx, -1), seg_start)
        post_excl = (wpos_incl >= 0) & ~win
        val_s = jnp.where(post_excl[:, None],
                          s_desired[jnp.maximum(wpos_incl, 0)], init_vals)
        verpt_s = ver0 + jnp.where(post_excl, jnp.uint32(2), jnp.uint32(0))
        w_idx = jnp.where(win, s_slot, n)
        new_data = data.at[w_idx].set(s_desired, mode="drop")
        new_version = version.at[w_idx].add(jnp.uint32(2), mode="drop")
        return new_data, new_version, val_s, verpt_s, win

    new_data, new_version, val_s, verpt_s, succ_s = lax.cond(
        jnp.any(is_valcas), _general, _fast, data, version)

    # --- per-lane results ---------------------------------------------------
    is_read = (s_kind == LOAD) | (s_kind == LL)
    vl_ok = s_clnk & (s_cslot == s_slot) & (s_cver == verpt_s)
    s_success = jnp.where(
        is_read | (s_kind == STORE), s_slot < n,
        jnp.where(s_kind == VALIDATE, vl_ok,
                  jnp.where(is_upd, succ_s, False)))
    s_value = jnp.where((s_kind != IDLE)[:, None], val_s,
                        jnp.zeros_like(val_s))

    # --- link context updates ----------------------------------------------
    is_ll = (s_kind == LL) & (s_slot < n)
    n_slot = jnp.where(is_ll, s_slot, s_cslot)
    n_ver = jnp.where(is_ll, verpt_s, s_cver)
    n_val = jnp.where(is_ll[:, None], val_s, ctx.value[order])
    n_lnk = jnp.where(is_ll, True,
                      jnp.where(s_kind == SC, False, s_clnk))
    new_ctx = LinkCtx(n_slot[inv], n_ver[inv], n_val[inv], n_lnk[inv])
    result = ApplyResult(s_value[inv], s_success[inv])

    # --- stats (the shared sorted-order definition) --------------------------
    stats = stats_on_sorted(n, s_slot, s_kind, succ_s)
    return new_data, new_version, new_ctx, result, stats


# ---------------------------------------------------------------------------
# Txn-group lane metadata: conflict arbitration for multi-lane transactions.
# ---------------------------------------------------------------------------

def arbitrate_groups(slot, group, eligible, *, n: int, n_groups: int):
    """The linearizer's lane-order rule lifted to whole lane GROUPS.

    A transaction (`repro.txn.mcas`) is a group of lanes that must commit
    all-or-nothing.  Within one batch the engine arbitrates single lanes by
    lane order (first eligible SC per cell wins); for groups the same rule
    becomes: the lowest-id eligible group claiming a cell wins that cell,
    and a group is a WINNER iff it wins every cell it claims.  Winners are
    therefore pairwise cell-disjoint, so a pure-SC commit batch of all their
    lanes resolves on the engine's one-round fast path with every SC
    succeeding — no descriptors, no helping.

    The lowest-id eligible group always wins all its cells, so arbitration
    guarantees progress (>= 1 group resolves per round).

    slot:     int32[p]  claimed cell per lane (out-of-range = unused lane)
    group:    int32[p]  owning group id per lane, in [0, n_groups)
    eligible: bool[p]   lane belongs to a group contending this round

    Returns bool[n_groups]: the winner mask.
    """
    slot = jnp.asarray(slot, jnp.int32)
    group = jnp.asarray(group, jnp.int32)
    in_range = (slot >= 0) & (slot < n)
    live = eligible & in_range
    claim = jnp.where(live, slot, n)
    gid = jnp.where(live, group, n_groups)
    # Lowest eligible group id per claimed cell (scatter-min).
    cell_min = jnp.full((n + 1,), n_groups, jnp.int32)
    cell_min = cell_min.at[claim].min(gid, mode="drop")
    lane_wins = cell_min[jnp.minimum(claim, n)] == group
    # A group wins iff ALL its live lanes win (scatter-AND via min).
    grp = jnp.ones((n_groups + 1,), jnp.int32)
    grp = grp.at[gid].min(lane_wins.astype(jnp.int32), mode="drop")
    return grp[:n_groups] > 0


# ---------------------------------------------------------------------------
# Round lowering: strategies may swap `linearize` for a fused kernel round.
# ---------------------------------------------------------------------------

def _engine_round():
    from repro.kernels import engine_round  # lazy: kernels import engine
    return engine_round


def round_for(spec: AtomicSpec, impl=None, mode: str | None = None):
    """The execution round for `spec`: the strategy's lowered kernel round
    (DESIGN.md §8) when it provides one and the engine-kernel mode allows
    it, else the pure-XLA `linearize`.  The returned callable has the exact
    `linearize` signature and is resolved at trace time (spec is static).

    Jitted callers must thread `mode` through as a static argument (see
    `_apply`) so a mid-process BIGATOMIC_ENGINE_KERNEL change can never hit
    a stale trace of the other engine."""
    mode, interpret = _engine_round().resolved_mode(mode)
    if mode == "off":
        return linearize
    if impl is None:
        impl = registry.get_strategy(spec.strategy)
    lowered = impl.lower_round(spec, mode=mode, interpret=interpret)
    return linearize if lowered is None else lowered


def canonicalize_ops(ops: OpBatch) -> OpBatch:
    """Coerce an op batch to the canonical dtypes (int32 kinds/slots, uint32
    words, no weak types) so equal-shaped batches can never retrace the
    jitted round (tests/test_engine_round.py asserts this with the
    `repro.analysis.tracing` counter)."""
    return OpBatch(jnp.asarray(ops.kind, jnp.int32),
                   jnp.asarray(ops.slot, jnp.int32),
                   jnp.asarray(ops.expected, WORD_DTYPE),
                   jnp.asarray(ops.desired, WORD_DTYPE))


def canonicalize_ctx(ctx: LinkCtx) -> LinkCtx:
    return LinkCtx(jnp.asarray(ctx.slot, jnp.int32),
                   jnp.asarray(ctx.version, jnp.uint32),
                   jnp.asarray(ctx.value, WORD_DTYPE),
                   jnp.asarray(ctx.linked, bool))


# ---------------------------------------------------------------------------
# The single public entry point: apply(spec, state, ops [, ctx]).
# ---------------------------------------------------------------------------

def check_kinds(kind, allowed, what: str) -> None:
    """Reject op kinds outside `allowed` when `kind` is concrete (traced
    kinds are the caller's contract — the oracle would raise on them)."""
    try:
        kind_np = np.asarray(kind)
    except jax.errors.TracerArrayConversionError:
        return
    bad = np.setdiff1d(kind_np, np.asarray(allowed))
    if bad.size:
        raise ValueError(f"op kinds {bad.tolist()} are not {what} ops "
                         f"(allowed: {sorted(allowed)})")


def _apply_impl(spec: AtomicSpec, state, ops: OpBatch, ctx: LinkCtx | None,
                mode: str, telem=None):
    impl = registry.get_strategy(spec.strategy)
    if ctx is None:
        ctx = init_ctx(ops.p, spec.k)
    round_fn = round_for(spec, impl, mode)
    new_data, new_version, new_ctx, result, stats = round_fn(
        impl.engine_view(state), state.version, ctx, ops)
    new_state = impl.commit(state, new_data, new_version,
                            stats.n_updates, ops.p)
    traffic = impl.traffic(stats, spec.k, ops.p)
    if telem is None:
        # BIGATOMIC_OBS=off: None is an empty pytree, so this traces the
        # exact pre-observability program (tests/test_obs.py asserts it).
        return new_state, new_ctx, result, stats, traffic
    eligible, taken = _engine_round().path_counts(
        spec.n, ops, fused=round_fn is not linearize)
    telem = obs_telemetry.count_table(telem, spec.n, ops, result, stats,
                                      eligible=eligible, taken=taken)
    return new_state, new_ctx, result, stats, traffic, telem


# The engine-kernel mode rides the jit cache key, so flipping
# BIGATOMIC_ENGINE_KERNEL mid-process retraces instead of silently reusing
# the other engine's compiled round.
_apply = functools.partial(jax.jit,
                           static_argnames=("spec", "mode"))(_apply_impl)
# Donating twin: hands the state buffers to XLA so the round updates them in
# place instead of copying the table once per call.  Correct only when the
# caller treats the passed state as dead; `apply(donate=True)` routes here
# (off-CPU only — the CPU runtime cannot donate and would warn every call).
_apply_donated = functools.partial(
    jax.jit, static_argnames=("spec", "mode"),
    donate_argnums=(1,))(_apply_impl)


def apply(spec: AtomicSpec, state, ops: OpBatch, ctx: LinkCtx | None = None,
          *, donate: bool = False):
    """Linearize `ops` against the table; maintain the strategy's layout.

    `spec` is the only static argument; `state`, `ops` and `ctx` are pure
    pytrees, so this call composes with `jax.jit`, `lax.scan`, donation and
    `shard_map`.  `ctx` carries per-lane LL/SC links across batches; omit it
    for batches without LL/SC/VALIDATE lanes.  Hash kinds (FIND/INSERT/
    DELETE) belong to `cachehash.apply_hash`, not here.

    Op/ctx leaves are canonicalized (int32 kinds/slots, uint32 words) before
    dispatch, so differently-typed but equal-shaped batches reuse one trace.
    `donate=True` additionally donates the state buffers to the jitted
    round (one fewer full table copy per call); the passed `state` must not
    be reused afterwards.  Donation is skipped on CPU backends, which
    cannot donate.

    Returns (state', ctx', ApplyResult, ApplyStats, Traffic)."""
    check_kinds(ops.kind, TABLE_KINDS, "table")
    ops = canonicalize_ops(ops)
    if ctx is not None:
        ctx = canonicalize_ctx(ctx)
    mode = _engine_round().configured_mode()
    # Under BIGATOMIC_OBS=counters the global counter pytree rides the same
    # jit call as one extra argument/output (no extra dispatch); when off —
    # or when an outer jit owns this call — telem is None and the traced
    # program is byte-identical to the pre-observability one.
    telem = obs_telemetry.carry_in(state, ops.kind)
    fn = (_apply_donated if donate and jax.default_backend() != "cpu"
          else _apply)
    out = fn(spec, state, ops, ctx, mode, telem)
    if telem is not None:
        *out, telem = out
        obs_telemetry.carry_out(telem)
        return tuple(out)
    return out


class RoundHandle:
    """A dispatched-but-not-awaited engine round (DESIGN.md §9).

    JAX arrays are futures under async dispatch, so `apply_round` returns
    the moment the round is enqueued; the handle names the five outputs and
    lets an executor overlap the NEXT batch's host-side route/pack work with
    this round's device compute.  `state`/`ctx` may be chained into the next
    `apply_round` immediately (XLA sequences the data dependency); `wait()`
    blocks until every output buffer is resident."""

    __slots__ = ("state", "ctx", "result", "stats", "traffic")

    def __init__(self, state, ctx, result, stats, traffic):
        self.state = state
        self.ctx = ctx
        self.result = result
        self.stats = stats
        self.traffic = traffic

    def _leaves(self):
        return jax.tree_util.tree_leaves(
            (self.state, self.ctx, self.result, self.stats, self.traffic))

    def ready(self) -> bool:
        """True iff every output buffer is already resident (non-blocking;
        conservatively False if the runtime lacks `Array.is_ready`)."""
        return all(getattr(leaf, "is_ready", lambda: False)()
                   for leaf in self._leaves())

    def wait(self) -> "RoundHandle":
        jax.block_until_ready(self._leaves())
        return self


def apply_round(spec: AtomicSpec, state, ops: OpBatch,
                ctx: LinkCtx | None = None, *, donate: bool = False
                ) -> RoundHandle:
    """`apply` as an overlappable round: identical semantics, but the outputs
    come back wrapped in a `RoundHandle` the executor can hold in its
    in-flight window while it packs the next stream's batch."""
    return RoundHandle(*apply(spec, state, ops, ctx, donate=donate))


def init(spec: AtomicSpec, initial=None):
    """Build the initial `TableState` pytree for `spec`."""
    impl = registry.get_strategy(spec.strategy)
    data = (jnp.zeros((spec.n, spec.k), WORD_DTYPE) if initial is None
            else jnp.asarray(initial, WORD_DTYPE))
    if data.shape != (spec.n, spec.k):
        raise ValueError(f"initial shape {data.shape} != ({spec.n}, {spec.k})")
    return impl.init(spec.n, spec.k, spec.p_max, data)


@functools.partial(jax.jit, static_argnames=("spec",))
def _read(spec: AtomicSpec, state, slots, telem=None):
    impl = registry.get_strategy(spec.strategy)
    values, ok = impl.read(state, jnp.asarray(slots, jnp.int32))
    if telem is None:
        return values, ok
    return values, ok, obs_telemetry.count_read(telem, ok)


def read(spec: AtomicSpec, state, slots):
    """Honest per-strategy read protocol.  Returns (values[q, k], ok[q]).

    ok=False means the reader observed a torn/locked cell and must retry
    (blocking strategies only); lock-free strategies always return ok=True
    with a consistent value.  Under BIGATOMIC_OBS=counters the retry count
    accumulates into `obs` as `read.torn_retries` (same jitted call)."""
    telem = obs_telemetry.carry_in(state, slots)
    if telem is None:
        return _read(spec, state, slots)
    values, ok, telem = _read(spec, state, slots, telem)
    obs_telemetry.carry_out(telem)
    return values, ok


@functools.partial(jax.jit, static_argnames=("spec",))
def logical(spec: AtomicSpec, state):
    """The current logical value of every cell, derived from the layout."""
    return registry.get_strategy(spec.strategy).logical(state)
