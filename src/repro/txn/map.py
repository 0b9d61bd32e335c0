"""Optimistic transactional map over CacheHash (DESIGN.md §7).

A map transaction declares a READ SET (keys whose values it observes) and a
WRITE SET (keys it upserts or deletes, with write values computed by a
traceable function of the read values).  A batch of T transactions executes
serializably: every committed transaction behaves as if its reads and
writes happened atomically at its commit point, in the claimed order
(commit round, then txn id).

Protocol, per attempt round (optimistic concurrency control, batch-step):

  1. read       one CacheHash FIND batch fetches every contending txn's
                read set.
  2. compute    `fn(read_values, read_found) -> write_values` (traced once).
  3. arbitrate  a txn wins iff no lower-id contending txn touches any of
                its written keys (read OR write) and no lower-id txn
                writes any of its read keys — two scatter-mins over the
                bucket domain (conservative: bucket-granular, exact on
                distinct buckets).  Winners are pairwise conflict-free, so
                their reads stay valid through every same-round commit.
  4. validate   winners re-FIND their read sets and compare against step 1
                (the OCC validation read; with no foreign traffic between
                batches it always passes — the code path is the contract).
  5. commit     ONE hash batch: DELETE lanes then INSERT lanes in lane
                order — CacheHash linearizes per bucket in lane order, so
                delete-then-insert is an atomic upsert; pure deletes skip
                the INSERT lane.

Losers retry after Dice-style backoff (`sync.queue.BackoffPolicy`); the
lowest contending txn id always wins, so every round commits at least one
txn and the loop terminates.  The single-device driver runs entirely under
`lax.while_loop` (spec/policy/max_rounds are the only statics); the
mesh-sharded driver (`transact_dist`) runs the same round logic host-side
over `core.distributed.apply_hash`, so cross-shard transactions linearize
through the key-owner-routed collective.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import cachehash as ch
from repro.core import engine
from repro.core.layout import WORD_DTYPE
from repro.core.specs import HashSpec
from repro.sync.queue import BackoffPolicy
from repro.txn.mcas import _policy_delay, max_rounds_bound


class MapTxns(NamedTuple):
    """T map transactions (a pure pytree).

    read_key:    uint32[T, R]  keys observed (masked by read_mask)
    read_mask:   bool[T, R]
    write_key:   uint32[T, W]  keys written (masked by write_mask)
    write_mask:  bool[T, W]
    write_del:   bool[T, W]    True = delete the key; False = upsert
    write_value: word[T, W, vw] upsert values used when `fn is None`
                 (data-carrying transactions, e.g. the serving bookkeeping
                 txn; ignored when a compute `fn` is supplied)
    """

    read_key: jax.Array
    read_mask: jax.Array
    write_key: jax.Array
    write_mask: jax.Array
    write_del: jax.Array
    write_value: jax.Array

    @property
    def t(self) -> int:
        return self.read_key.shape[0]


class MapResult(NamedTuple):
    """read_value/read_found: each txn's read set AS OBSERVED at its commit
    point; round: 1-based commit round; attempts: arbitration losses;
    rounds: total rounds the batch took."""

    read_value: jax.Array
    read_found: jax.Array
    round: jax.Array
    attempts: jax.Array
    rounds: jax.Array


def make_map_txns(read_key, write_key, *, read_mask=None, write_mask=None,
                  write_del=None, write_value=None, vw: int = 1) -> MapTxns:
    """Checked constructor: rank-2 key arrays sharing T, masks matching,
    no duplicate live write keys within one transaction.  `write_value`
    ([T, W, vw], coerced to words) feeds fn-less transactions; it defaults
    to zeros of width `vw`."""
    read_key = jnp.asarray(read_key, jnp.uint32)
    write_key = jnp.asarray(write_key, jnp.uint32)
    if read_key.ndim != 2 or write_key.ndim != 2:
        raise ValueError(f"keys must be rank-2 [T, ...]: read "
                         f"{read_key.shape}, write {write_key.shape}")
    t, r = read_key.shape
    tw, w = write_key.shape
    if tw != t:
        raise ValueError(f"read/write txn counts differ: {t} vs {tw}")

    def mask(m, shape, default):
        if m is None:
            return jnp.full(shape, default, bool)
        m = jnp.asarray(m, bool)
        if m.shape != shape:
            raise ValueError(f"mask shape {m.shape} != {shape}")
        return m

    read_mask = mask(read_mask, (t, r), True)
    write_mask = mask(write_mask, (t, w), True)
    write_del = mask(write_del, (t, w), False)
    if write_value is None:
        write_value = jnp.zeros((t, w, vw), WORD_DTYPE)
    else:
        write_value = jnp.asarray(write_value, WORD_DTYPE)
        if write_value.ndim != 3 or write_value.shape[:2] != (t, w):
            raise ValueError(f"write_value shape {write_value.shape} != "
                             f"({t}, {w}, vw)")
    try:
        wk, wm = np.asarray(write_key), np.asarray(write_mask)
    except jax.errors.TracerArrayConversionError:
        wk = None
    if wk is not None:
        for i in range(t):
            live = wk[i][wm[i]]
            if len(np.unique(live)) != len(live):
                raise ValueError(f"transaction {i} writes duplicate keys: "
                                 f"{sorted(live.tolist())}")
    return MapTxns(read_key, read_mask, write_key, write_mask, write_del,
                   write_value)


def _winners(txns: MapTxns, active, nb: int):
    """Conflict arbitration over the bucket domain: txn i wins iff
    (a) no active j < i reads-or-writes any bucket i writes, and
    (b) no active j < i writes any bucket i reads.  The winner set is
    pairwise conflict-free and always contains the lowest active id."""
    t = txns.t
    gid = jnp.arange(t, dtype=jnp.int32)

    def bucket(keys):
        return (ch.hash_u32(keys) & jnp.uint32(nb - 1)).astype(jnp.int32)

    def scatter_min(b, mask):
        flat_b = jnp.where(mask, b, nb).reshape(-1)
        flat_g = jnp.where(mask, gid[:, None], t).reshape(-1)
        out = jnp.full((nb + 1,), t, jnp.int32)
        return out.at[flat_b].min(flat_g, mode="drop")

    rb = bucket(txns.read_key)
    wb = bucket(txns.write_key)
    r_live = txns.read_mask & active[:, None]
    w_live = txns.write_mask & active[:, None]
    wmin = scatter_min(wb, w_live)               # lowest active WRITER
    amin = jnp.minimum(wmin, scatter_min(rb, r_live))  # lowest active TOUCHER

    def per_txn_ok(cond, mask):
        return jnp.all(cond | ~mask, axis=1)

    ok_w = per_txn_ok(amin[jnp.minimum(wb, nb)] >= gid[:, None], w_live)
    ok_r = per_txn_ok(wmin[jnp.minimum(rb, nb)] >= gid[:, None], r_live)
    return active & ok_w & ok_r


def _round(happly, spec: HashSpec, txns: MapTxns, fn, state, active):
    """One OCC attempt round (pure jnp; shared by the jitted single-device
    driver and the host-side sharded driver).  Returns
    (state', committed[T], read_value[T,R,vw], read_found[T,R])."""
    t, vw = txns.t, spec.vw
    r = txns.read_key.shape[1]
    w = txns.write_key.shape[1]
    rk = txns.read_key.reshape(t * r)
    r_act = (txns.read_mask & active[:, None]).reshape(t * r)

    # 1. read ---------------------------------------------------------------
    state, res = happly(state, ch.make_hash_ops(
        jnp.where(r_act, engine.FIND, engine.IDLE), rk, vw=vw))
    rv = res.value.reshape(t, r, vw)
    rf = res.found.reshape(t, r)

    # 2. compute (fn=None: the txns carry their write values) ---------------
    wv = txns.write_value if fn is None else jnp.asarray(fn(rv, rf),
                                                         WORD_DTYPE)
    if wv.shape != (t, w, vw):
        raise ValueError(f"fn returned shape {wv.shape}, want "
                         f"({t}, {w}, {vw})")

    # 3. arbitrate ----------------------------------------------------------
    winner = _winners(txns, active, spec.nb)

    # 4. validate (winners re-read; must equal step 1) ----------------------
    v_act = (txns.read_mask & winner[:, None]).reshape(t * r)
    state, vres = happly(state, ch.make_hash_ops(
        jnp.where(v_act, engine.FIND, engine.IDLE), rk, vw=vw))
    vf = vres.found.reshape(t, r)
    vvals = vres.value.reshape(t, r, vw)
    same = (vf == rf) & (jnp.all(vvals == rv, axis=2) | ~rf)
    confirmed = winner & jnp.all(same | ~txns.read_mask, axis=1)

    # 5. commit: DELETE lanes then INSERT lanes, one batch ------------------
    wk = txns.write_key.reshape(t * w)
    d_lane = (txns.write_mask & confirmed[:, None]).reshape(t * w)
    i_lane = d_lane & ~txns.write_del.reshape(t * w)
    kinds = jnp.concatenate([
        jnp.where(d_lane, engine.DELETE, engine.IDLE),
        jnp.where(i_lane, engine.INSERT, engine.IDLE)])
    keys = jnp.concatenate([wk, wk])
    vals = jnp.concatenate([jnp.zeros((t * w, vw), WORD_DTYPE),
                            wv.reshape(t * w, vw)])
    state, _ = happly(state, ch.make_hash_ops(kinds, keys, vals, vw=vw))
    return state, confirmed, rv, rf


@functools.partial(jax.jit,
                   static_argnames=("spec", "fn", "policy", "max_rounds"))
def _transact(spec: HashSpec, state, txns: MapTxns, fn,
              policy: BackoffPolicy, max_rounds: int):
    t, vw = txns.t, spec.vw
    r = txns.read_key.shape[1]

    def happly(st, ops):
        st, res, _ = ch.apply_hash(spec, st, ops)
        return st, res

    def body(carry):
        rnd, state, pending, round_res, attempts, delay, orv, orf = carry
        rnd = rnd + 1
        active = pending & (delay <= 0)
        state, committed, rv, rf = _round(happly, spec, txns, fn, state,
                                          active)
        orv = jnp.where(committed[:, None, None], rv, orv)
        orf = jnp.where(committed[:, None], rf, orf)
        round_res = jnp.where(committed, rnd, round_res)
        pending = pending & ~committed
        lost = active & ~committed
        attempts = attempts + lost.astype(jnp.int32)
        delay = jnp.where(lost, _policy_delay(policy, attempts),
                          jnp.maximum(delay - 1, 0))
        return rnd, state, pending, round_res, attempts, delay, orv, orf

    init = (jnp.int32(0), state, jnp.ones((t,), bool),
            jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32),
            jnp.zeros((t,), jnp.int32),
            jnp.zeros((t, r, vw), WORD_DTYPE), jnp.zeros((t, r), bool))
    out = lax.while_loop(
        lambda c: (c[0] < max_rounds) & jnp.any(c[2]), body, init)
    rnd, state, _pending, round_res, attempts, _delay, orv, orf = out
    return state, MapResult(orv, orf, round_res, attempts, rnd)


def transact(spec: HashSpec, state, txns: MapTxns, fn, *,
             policy: BackoffPolicy = BackoffPolicy("none"),
             max_rounds: int | None = None):
    """Run a batch of map transactions to serializable commit.

    `fn(read_values[T,R,vw], read_found[T,R]) -> write_values[T,W,vw]` must
    be traceable (it runs under `jax.jit` inside the retry loop) and
    hashable (a module-level function or functools.partial — it is a static
    argument).  Returns (state', MapResult); the claimed serialization is
    `linearization_order(result)`."""
    if max_rounds is None:
        max_rounds = max_rounds_bound(txns.t, policy)
    return _transact(spec, state, txns, fn, policy, max_rounds)


def transact_dist(mesh, dspec, dstate, txns: MapTxns, fn, *,
                  policy: BackoffPolicy = BackoffPolicy("none"),
                  max_rounds: int | None = None):
    """`transact` over a mesh-sharded CacheHash: identical round logic, but
    every hash batch routes by key owner through `distributed.apply_hash`
    (host-side retry driver — the collective is the jitted part), so
    transactions whose read/write sets span shards commit atomically."""
    from repro.core import distributed as dsb
    hs: HashSpec = dspec.inner
    if max_rounds is None:
        max_rounds = max_rounds_bound(txns.t, policy)

    def happly(st, ops):
        q = ops.kind.shape[0]
        q_pad = -(-q // dspec.n_shards) * dspec.n_shards
        d = dataclasses.replace(dspec, p_local=q_pad // dspec.n_shards,
                                route_capacity=q_pad)
        st, res, _ovf = dsb.apply_hash(mesh, d, st, ops)
        # Materialize results on the host before the round logic reuses
        # them: the collective's outputs carry the mesh sharding (claimed
        # replicated over spare axes under check_vma=False), and eager
        # re-use in jnp ops would re-reduce those "replicas".
        return st, type(res)(*[np.asarray(x) for x in res])

    t, vw = txns.t, hs.vw
    r = txns.read_key.shape[1]
    pending = np.ones((t,), bool)
    round_res = np.zeros((t,), np.int32)
    attempts = np.zeros((t,), np.int32)
    delay = np.zeros((t,), np.int32)
    orv = np.zeros((t, r, vw), np.uint32)
    orf = np.zeros((t, r), bool)
    rnd = 0
    while pending.any() and rnd < max_rounds:
        rnd += 1
        active = pending & (delay <= 0)
        if not active.any():
            delay = np.maximum(delay - 1, 0)
            continue
        dstate, committed, rv, rf = _round(happly, hs, txns, fn, dstate,
                                           jnp.asarray(active))
        committed = np.asarray(committed)
        orv = np.where(committed[:, None, None], np.asarray(rv), orv)
        orf = np.where(committed[:, None], np.asarray(rf), orf)
        round_res = np.where(committed, rnd, round_res)
        pending &= ~committed
        lost = active & ~committed
        attempts = attempts + lost.astype(np.int32)
        delay = np.maximum(delay - 1, 0)
        for i in np.nonzero(lost)[0]:
            delay[i] = policy.delay(int(attempts[i]))
    if pending.any():
        raise RuntimeError(f"transact_dist round bound exceeded "
                           f"({max_rounds}); pending="
                           f"{np.nonzero(pending)[0].tolist()}")
    return dstate, MapResult(orv, orf, round_res, attempts, rnd)


def linearization_order(result: MapResult) -> np.ndarray:
    """Txn ids in the claimed serialization: commit round, then txn id."""
    rnd = np.asarray(result.round)
    ids = np.arange(rnd.shape[0])
    return ids[np.lexsort((ids, rnd))]


def transact_reference(model: dict, txns: MapTxns, fn, order, vw: int):
    """Sequential replay defining the semantics: apply whole transactions
    one at a time in `order` against a dict model.  Returns
    (model', read_value[T,R,vw], read_found[T,R])."""
    rk = np.asarray(txns.read_key)
    rm = np.asarray(txns.read_mask)
    wk = np.asarray(txns.write_key)
    wm = np.asarray(txns.write_mask)
    wd = np.asarray(txns.write_del)
    t, r = rk.shape
    w = wk.shape[1]
    out_v = np.zeros((t, r, vw), np.uint32)
    out_f = np.zeros((t, r), bool)
    for i in np.asarray(order, np.int64):
        rv = np.zeros((1, r, vw), np.uint32)
        rf = np.zeros((1, r), bool)
        for j in range(r):
            if rm[i, j] and int(rk[i, j]) in model:
                rv[0, j] = model[int(rk[i, j])]
                rf[0, j] = True
        wv = np.asarray(txns.write_value)[i] if fn is None else \
            np.asarray(fn(jnp.asarray(rv), jnp.asarray(rf)))[0]
        for j in range(w):
            if not wm[i, j]:
                continue
            key = int(wk[i, j])
            if wd[i, j]:
                model.pop(key, None)
            else:
                model[key] = np.asarray(wv[j], np.uint32).copy()
        out_v[i], out_f[i] = rv[0], rf[0]
    return model, out_v, out_f
