"""Batched k-word MCAS over big atomics — multi-location atomicity on the
unified engine (DESIGN.md §7).

A transaction is a group of up to W (slot, expected, desired) lanes that
commit ALL-OR-NOTHING: if every claimed cell still holds its expected value
at the transaction's linearization point, all W desired values are written
(each cell's version bumps by 2, exactly as a store); otherwise nothing is
written and the transaction reports failure with the witnessed values.
This is the CAS-semantics MCAS of Blelloch & Wei ("LL/SC and Atomic Copy",
arXiv:1911.09671): multi-word atomicity built from LL/SC, with NO
descriptors — the batch-step engine arbitrates conflicts directly.

Protocol, per attempt round (all three batches ride `engine.linearize`
through the strategy registry, so every layout gets MCAS for free):

  1. LL-all       every lane of every contending txn load-links its cell.
                  A lane whose value != expected fails its whole txn NOW
                  (the txn linearizes at this read — the failure witness).
  2. VALIDATE-all surviving txns validate every link (a pure VALIDATE
                  batch; honesty round — links can only die if a caller
                  interleaves foreign traffic between engine batches).
  3. arbitrate    `engine.arbitrate_groups`: lowest txn id claiming a cell
                  wins it; a txn is a winner iff it wins EVERY cell it
                  claims.  Winners are pairwise cell-disjoint.
  4. SC-commit    ONE pure-SC batch commits every winner lane — the
                  engine's one-round fast path (every link predates the
                  batch and winners never share a cell, so every SC
                  succeeds).

Losers (ready but out-arbitrated) retry after a Dice-style abort backoff
(`repro.sync.queue.BackoffPolicy`, the queue's contention-management module,
arXiv:1305.5800) measured in rounds.  The lowest pending txn id always wins
arbitration, so every round either fails or commits at least one txn:
termination is guaranteed within `max_rounds`.

The CLAIMED linearization: round-major, failures before commits within a
round, txn id within each class — `linearization_order(result)` emits it
for the `TxnOracle` harness (tests/oracle.py), and `mcas_reference` is the
sequential replay that defines the semantics.

Everything is a pure pytree under one `jax.jit` (`spec`, the backoff policy
and `max_rounds` are the only statics), so `mcas` composes with `lax.scan`,
donation and `shard_map`; the mesh-sharded two-round prepare/commit variant
lives in `core.distributed.mcas`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import engine, registry
from repro.core.layout import WORD_DTYPE
from repro.core.specs import AtomicSpec
from repro.obs import telemetry as obs_telemetry
from repro.sync.queue import BackoffPolicy


class TxnBatch(NamedTuple):
    """T transactions of up to W lanes each (a pure pytree).

    slot:     int32[T, W]  claimed cell per lane; -1 = unused (txn width < W)
    expected: word[T, W, k]  per-lane comparand
    desired:  word[T, W, k]  per-lane value to install on commit
    """

    slot: jax.Array
    expected: jax.Array
    desired: jax.Array

    @property
    def t(self) -> int:
        return self.slot.shape[0]

    @property
    def w(self) -> int:
        return self.slot.shape[1]


class McasResult(NamedTuple):
    """Per-transaction results of one `mcas` call.

    success:  bool[T]     txn committed (all lanes written atomically)
    witness:  word[T,W,k] value of each claimed cell at the txn's
                          linearization point (failed txns: the mismatching
                          read; committed txns: the pre-write values)
    round:    int32[T]    1-based attempt round at which the txn resolved
    attempts: int32[T]    arbitration losses before resolving
    rounds:   int32[]     total rounds the batch took
    """

    success: jax.Array
    witness: jax.Array
    round: jax.Array
    attempts: jax.Array
    rounds: jax.Array


def make_txns(slot, expected=None, desired=None, *, k: int) -> TxnBatch:
    """THE checked transaction constructor (mirrors `engine.make_ops`).

    Checks (on concrete inputs): slot is rank-2 [T, W]; expected/desired are
    [T, W, k] (a mismatched trailing dim is the "mismatched k" error);
    no duplicate live slots within one transaction.  Word payloads coerce
    to the canonical WORD_DTYPE."""
    slot = jnp.asarray(slot, jnp.int32)
    if slot.ndim != 2:
        raise ValueError(f"slot must be rank-2 [T, W], got shape "
                         f"{slot.shape}")
    t, w = slot.shape
    if t == 0 or w == 0:
        raise ValueError(f"need at least one transaction lane: {slot.shape}")
    if expected is None:
        expected = jnp.zeros((t, w, k), WORD_DTYPE)
    else:
        expected = jnp.asarray(expected, WORD_DTYPE)
    if desired is None:
        desired = jnp.zeros((t, w, k), WORD_DTYPE)
    else:
        desired = jnp.asarray(desired, WORD_DTYPE)
    for name, arr in (("expected", expected), ("desired", desired)):
        if arr.shape != (t, w, k):
            raise ValueError(f"{name} shape {arr.shape} != ({t}, {w}, {k}) "
                             f"(mismatched k?)")
    try:
        slot_np = np.asarray(slot)          # concrete only; tracers skip
    except jax.errors.TracerArrayConversionError:
        slot_np = None
    if slot_np is not None:
        for i in range(t):
            live = slot_np[i][slot_np[i] >= 0]
            if len(np.unique(live)) != len(live):
                raise ValueError(f"transaction {i} claims duplicate slots: "
                                 f"{sorted(live.tolist())}")
    return TxnBatch(slot, expected, desired)


def _policy_delay(policy: BackoffPolicy, attempts: jax.Array) -> jax.Array:
    """`policy.delay` as a traced expression (policy fields are static)."""
    if policy.kind == "none":
        return jnp.zeros_like(attempts)
    if policy.kind == "const":
        return jnp.full_like(attempts, policy.base)
    if policy.kind == "exp":
        e = jnp.clip(attempts - 1, 0, 16)
        return jnp.minimum(jnp.left_shift(jnp.int32(policy.base), e),
                           jnp.int32(policy.cap))
    raise ValueError(f"unknown backoff kind {policy.kind!r}")


def max_rounds_bound(t: int, policy: BackoffPolicy) -> int:
    """Rounds after which every txn has provably resolved: >= 1 txn resolves
    per backoff window, and a window is at most max-delay + 1 rounds."""
    max_delay = {"none": 0, "const": policy.base, "exp": policy.cap}
    return t * (max_delay.get(policy.kind, policy.cap) + 2) + 4


class McasCarry(NamedTuple):
    """The protocol state between attempt rounds (a pure pytree) — identical
    to `_mcas`'s while_loop carry minus the table state, so one cooperative
    `mcas_round` step is BIT-IDENTICAL to one iteration of the fused loop.

    r:         int32[]     rounds run so far
    pending:   bool[T]     txns not yet resolved
    success:   bool[T]     txns committed
    witness:   word[T*W,k] flattened per-lane witness values
    round_res: int32[T]    1-based round each txn resolved in (0 = pending)
    attempts:  int32[T]    arbitration losses so far
    delay:     int32[T]    backoff rounds left before the txn re-contends
    """

    r: jax.Array
    pending: jax.Array
    success: jax.Array
    witness: jax.Array
    round_res: jax.Array
    attempts: jax.Array
    delay: jax.Array


def _round_step(spec: AtomicSpec, impl, round_fn, state, txns: TxnBatch,
                carry: McasCarry, policy: BackoffPolicy, telem=None):
    """ONE attempt round (LL-all / VALIDATE-all / arbitrate / SC-commit):
    the single traced body both `_mcas`'s while_loop and the cooperative
    `mcas_round` run, so yielding to a scheduler between rounds cannot
    change any result.

    `telem` (BIGATOMIC_OBS=counters) accumulates the protocol's own
    bookkeeping masks — committed / failed_now / lost — into the mcas.*
    counters and rides the return as a third element; None keeps the
    pre-observability two-element return and trace."""
    t, w, k, n = txns.t, txns.w, spec.k, spec.n
    p = t * w
    f_slot = txns.slot.reshape(p)
    f_exp = txns.expected.reshape(p, k)
    f_des = txns.desired.reshape(p, k)
    lane_txn = jnp.repeat(jnp.arange(t, dtype=jnp.int32), w)
    lane_used = (f_slot >= 0) & (f_slot < n)
    safe_slot = jnp.where(lane_used, f_slot, 0)

    def per_txn_all(flag_lane):
        """AND a per-lane flag over each txn's USED lanes (unused ⇒ True)."""
        return jnp.all((flag_lane | ~lane_used).reshape(t, w), axis=1)

    (r, pending, success, witness, round_res, attempts, delay) = carry
    r = r + 1
    active_t = pending & (delay <= 0)
    active_lane = active_t[lane_txn] & lane_used

    # 1. LL-all --------------------------------------------------------------
    ops1 = engine.OpBatch(
        jnp.where(active_lane, engine.LL, engine.IDLE), safe_slot,
        jnp.zeros((p, k), WORD_DTYPE), jnp.zeros((p, k), WORD_DTYPE))
    d1, v1, ctx, res1, st1 = round_fn(
        impl.engine_view(state), state.version,
        engine.init_ctx(p, k), ops1)
    state = impl.commit(state, d1, v1, st1.n_updates, p)
    vals = res1.value
    match_lane = jnp.all(vals == f_exp, axis=1)
    txn_match = per_txn_all(match_lane)
    failed_now = active_t & ~txn_match

    # 2. VALIDATE-all --------------------------------------------------------
    ready_lane = (active_t & txn_match)[lane_txn] & lane_used
    ops2 = engine.OpBatch(
        jnp.where(ready_lane, engine.VALIDATE, engine.IDLE), safe_slot,
        jnp.zeros((p, k), WORD_DTYPE), jnp.zeros((p, k), WORD_DTYPE))
    d2, v2, ctx, res2, st2 = round_fn(
        impl.engine_view(state), state.version, ctx, ops2)
    state = impl.commit(state, d2, v2, st2.n_updates, p)
    ready_t = active_t & txn_match & per_txn_all(res2.success)

    # 3. arbitrate -----------------------------------------------------------
    winner_t = ready_t & engine.arbitrate_groups(
        safe_slot, lane_txn, ready_t[lane_txn] & lane_used,
        n=n, n_groups=t)

    # 4. SC-commit (one round: pure-SC fast path, disjoint cells) ------------
    win_lane = winner_t[lane_txn] & lane_used
    ops3 = engine.OpBatch(
        jnp.where(win_lane, engine.SC, engine.IDLE), safe_slot,
        jnp.zeros((p, k), WORD_DTYPE), f_des)
    d3, v3, ctx, res3, st3 = round_fn(
        impl.engine_view(state), state.version, ctx, ops3)
    state = impl.commit(state, d3, v3, st3.n_updates, p)
    committed = winner_t & per_txn_all(res3.success)

    # 5. bookkeeping ---------------------------------------------------------
    resolved = failed_now | committed
    res_lane = resolved[lane_txn] & lane_used
    witness = jnp.where(res_lane[:, None], vals, witness)
    success = success | committed
    round_res = jnp.where(resolved, r, round_res)
    pending = pending & ~resolved
    lost = ready_t & ~committed
    attempts = attempts + lost.astype(jnp.int32)
    delay = jnp.where(lost, _policy_delay(policy, attempts),
                      jnp.maximum(delay - 1, 0))
    carry = McasCarry(r, pending, success, witness, round_res,
                      attempts, delay)
    if telem is None:
        return state, carry
    return state, carry, obs_telemetry.count_mcas_round(
        telem, committed, failed_now, lost)


@functools.partial(jax.jit,
                   static_argnames=("spec", "policy", "max_rounds", "mode"))
def _mcas(spec: AtomicSpec, state, txns: TxnBatch,
          policy: BackoffPolicy, max_rounds: int, mode: str, telem=None):
    impl = registry.get_strategy(spec.strategy)
    # Commit rounds ride the strategy's lowered kernel round (DESIGN.md §8):
    # the LL-all batch is collision-free under low contention and the SC
    # batch always is (winners are cell-disjoint), so both hit the fast
    # path.  `mode` is static so an engine-kernel env change retraces.
    round_fn = engine.round_for(spec, impl, mode)
    t, w, k = txns.t, txns.w, spec.k

    def body(c):
        return _round_step(spec, impl, round_fn, c[0], txns, c[1], policy,
                           *c[2:])

    init = ((state, mcas_begin(txns)) if telem is None
            else (state, mcas_begin(txns), telem))
    out = lax.while_loop(
        lambda c: (c[1].r < max_rounds) & jnp.any(c[1].pending), body, init)
    state, carry = out[0], out[1]
    result = McasResult(carry.success, carry.witness.reshape(t, w, k),
                        carry.round_res, carry.attempts, carry.r)
    if telem is None:
        return state, result
    return state, result, out[2]


def mcas(spec: AtomicSpec, state, txns: TxnBatch, *,
         policy: BackoffPolicy = BackoffPolicy("none"),
         max_rounds: int | None = None):
    """Commit a batch of k-word MCAS transactions against the table.

    `spec` / `policy` / `max_rounds` are the only statics; `state` and
    `txns` are pure pytrees.  Returns (state', McasResult); the claimed
    linearization order is `linearization_order(result)`.
    """
    if txns.expected.shape[2] != spec.k:
        raise ValueError(f"txn word width {txns.expected.shape[2]} != "
                         f"spec.k {spec.k}")
    if max_rounds is None:
        max_rounds = max_rounds_bound(txns.t, policy)
    mode = engine._engine_round().configured_mode()
    telem = obs_telemetry.carry_in(state, txns.slot)
    if telem is None:
        return _mcas(spec, state, txns, policy, max_rounds, mode)
    state, result, telem = _mcas(spec, state, txns, policy, max_rounds,
                                 mode, telem)
    obs_telemetry.carry_out(telem)
    return state, result


# ---------------------------------------------------------------------------
# Cooperative rounds: the SAME protocol advanced one round per call, so a
# scheduler (repro.runtime.executor) can run other streams' batches between
# contended retries instead of spinning inside one lax.while_loop.
# ---------------------------------------------------------------------------

def mcas_begin(txns: TxnBatch) -> McasCarry:
    """The fresh carry `_mcas` starts its while_loop from — hand it to
    `mcas_round` to run the identical protocol cooperatively."""
    t, w, k = txns.t, txns.w, txns.expected.shape[2]
    return McasCarry(jnp.int32(0), jnp.ones((t,), bool),
                     jnp.zeros((t,), bool),
                     jnp.zeros((t * w, k), WORD_DTYPE),
                     jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
                     jnp.zeros((t,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("spec", "policy", "mode"))
def _mcas_round(spec: AtomicSpec, state, txns: TxnBatch, carry: McasCarry,
                policy: BackoffPolicy, mode: str, telem=None):
    impl = registry.get_strategy(spec.strategy)
    round_fn = engine.round_for(spec, impl, mode)
    return _round_step(spec, impl, round_fn, state, txns, carry, policy,
                       telem)


def mcas_round(spec: AtomicSpec, state, txns: TxnBatch, carry: McasCarry, *,
               policy: BackoffPolicy = BackoffPolicy("none")):
    """Advance the MCAS protocol by ONE attempt round (LL-all /
    VALIDATE-all / arbitrate / SC-commit) and return (state', carry').

    Because links never span rounds (each round builds and consumes its own
    ctx), a caller may interleave ARBITRARY foreign batches against `state`
    between rounds — pending txns simply re-read on their next attempt.
    Driving this to `not carry.pending.any()` yields bit-identical results
    to `mcas` with the same policy; `mcas_finish` packages them.
    """
    if txns.expected.shape[2] != spec.k:
        raise ValueError(f"txn word width {txns.expected.shape[2]} != "
                         f"spec.k {spec.k}")
    mode = engine._engine_round().configured_mode()
    telem = obs_telemetry.carry_in(state, txns.slot)
    if telem is None:
        return _mcas_round(spec, state, txns, carry, policy, mode)
    state, carry, telem = _mcas_round(spec, state, txns, carry, policy,
                                      mode, telem)
    obs_telemetry.carry_out(telem)
    return state, carry


def mcas_finish(txns: TxnBatch, carry: McasCarry) -> McasResult:
    """Package a drained cooperative run as the standard `McasResult` (same
    contract as `mcas`, so `linearization_order` and the TxnOracle apply)."""
    t, w, k = txns.t, txns.w, txns.expected.shape[2]
    return McasResult(carry.success, carry.witness.reshape(t, w, k),
                      carry.round_res, carry.attempts, carry.r)


# ---------------------------------------------------------------------------
# The claimed order + the sequential replay that defines the semantics.
# ---------------------------------------------------------------------------

def linearization_order(result: McasResult) -> np.ndarray:
    """Txn ids in the claimed linearization: round-major, failures before
    commits within a round (failures witness the pre-commit values), txn id
    within each class.  Txns that never resolved (round == 0, possible only
    under a caller-supplied `max_rounds` below the provable bound) never
    executed and are excluded — the oracle treats them as dropped."""
    rnd = np.asarray(result.round)
    suc = np.asarray(result.success).astype(np.int64)
    ids = np.arange(rnd.shape[0])
    order = ids[np.lexsort((ids, suc, rnd))]
    return order[rnd[order] > 0]


def mcas_reference(data: np.ndarray, version: np.ndarray, txns: TxnBatch,
                   order) -> tuple:
    """Replay whole transactions one at a time in `order`.  Pure numpy.

    Returns (data', version', success[T], witness[T, W, k])."""
    data = np.array(data, copy=True)
    version = np.array(version, copy=True)
    slot = np.asarray(txns.slot)
    expected = np.asarray(txns.expected)
    desired = np.asarray(txns.desired)
    t, w, k = expected.shape
    success = np.zeros((t,), bool)
    witness = np.zeros((t, w, k), data.dtype)
    for i in np.asarray(order, np.int64):
        used = [j for j in range(w)
                if 0 <= slot[i, j] < data.shape[0]]
        ok = True
        for j in used:
            witness[i, j] = data[slot[i, j]]
            if not np.array_equal(data[slot[i, j]], expected[i, j]):
                ok = False
        if ok:
            for j in used:
                data[slot[i, j]] = desired[i, j]
                version[slot[i, j]] += 2
            success[i] = True
    return data, version, success, witness
