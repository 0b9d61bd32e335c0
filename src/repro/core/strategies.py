"""The paper's big-atomic memory layouts as registered `StrategyImpl`s.

Every strategy provides the *same* linearizable batch semantics (the unified
engine in `repro.core.engine`, property-tested against sequential oracles)
but a *different* memory layout, reader protocol, and traffic profile:

  SEQLOCK    data[n,k] + ver[n].            1 gather/load; blocking on torn state.
  INDIRECT   ptr[n] -> pool[n+2p, k].       2 *dependent* gathers per load; never blocks.
  CACHED_WF  cache[n,k] + ver[n] + bptr[n] -> pool[n+2p,k].  1 gather fast path,
             backup fallback on race; never blocks.  Space 2nk + O(pk).
  CACHED_ME  cache[n,k] + ver[n] + bptr[n](tagged null) -> pool[3p,k].  1 gather
             fast path; backup only *during* a race; space nk + O(pk).
  SIMPLOCK   data[n,k] + lock[n].           lock RMW on every op; blocks readers.
  PLAIN      data[n,k], no protocol.        negative control: returns torn data.

Node reclamation uses a FIFO ring of free slots — the deterministic analogue
of the paper's hazard-pointer/private-slab schemes (DESIGN.md §2).  Further
layouts plug in from anywhere via `registry.register_strategy` without
touching this file or the engine (DESIGN.md §5).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.layout import (NULL, TableState, Traffic, WORD_BYTES,
                               WORD_DTYPE, _empty, ring_alloc, ring_free,
                               sim_alloc)
from repro.core.registry import StrategyImpl, register_strategy


class _KernelLowering:
    """Mixin: lower the engine round to the fused fast/slow kernels
    (DESIGN.md §8).  The four paper layouts share the kernel — they all
    linearize against the same (engine_view, version) pair — but each owns
    its fast tier's lanes per grid step so a layout with wider cells can
    trade grid steps for VMEM.  PLAIN/SIMPLOCK
    and external plug-ins inherit the base `lower_round` (None) and stay on
    the pure-XLA reference path."""

    kernel_block: int = 8

    def lower_round(self, spec, *, mode: str, interpret: bool):
        from repro.kernels import engine_round
        return engine_round.make_round(spec.n, spec.k, mode=mode,
                                       interpret=interpret,
                                       block=self.kernel_block)


@register_strategy
class Plain(StrategyImpl):
    """Negative control: no protocol, readers may observe torn cells."""

    name = "plain"
    lock_free = False


class _Versioned(StrategyImpl):
    """Shared base for layouts that keep data[n,k] + an even/odd version."""

    def memory_bytes(self, n, k, p):
        return n * (k + 1) * WORD_BYTES

    def check_invariants(self, spec, state):
        # At a quiescent point every writer has unlocked: versions even.
        return {"version_parity": state.version % 2 != 0}


@register_strategy
class Seqlock(_KernelLowering, _Versioned):
    name = "seqlock"
    blocks_readers = True

    def read(self, state, slots):
        v1 = state.version[slots]
        val = state.data[slots]
        v2 = state.version[slots]
        ok = (v1 == v2) & (v1 % 2 == 0)
        return val, ok

    def traffic(self, stats, k, p):
        w = WORD_BYTES
        cell = k * w
        loads, raced, upd = stats.n_loads, stats.n_raced_loads, stats.n_updates
        br = loads * (cell + 2 * w) + raced * (cell + 2 * w) + upd * (cell + 2 * w)
        bw = upd * (cell + 2 * w)
        chains = jnp.where(raced > 0, 2, 1)
        return Traffic(jnp.asarray(br, jnp.float32), jnp.asarray(bw, jnp.float32),
                       jnp.asarray(chains, jnp.int32), jnp.asarray(upd, jnp.int32))

    def begin_update(self, state, slot, new_value, torn_words):
        half = state.data[slot].at[:torn_words].set(new_value[:torn_words])
        return state._replace(
            version=state.version.at[slot].add(jnp.uint32(1)),  # odd = locked
            data=state.data.at[slot].set(half))


@register_strategy
class Simplock(_Versioned):
    name = "simplock"
    blocks_readers = True

    def init(self, n, k, p_max, data):
        base = super().init(n, k, p_max, data)
        return base._replace(lock=jnp.zeros((n,), jnp.uint32))

    def read(self, state, slots):
        held = state.lock[slots] != 0
        return state.data[slots], ~held

    def traffic(self, stats, k, p):
        w = WORD_BYTES
        cell = k * w
        loads, upd = stats.n_loads, stats.n_updates
        br = (loads + upd) * (cell + w)
        bw = upd * cell + (loads + upd) * 2 * w        # lock/unlock writes
        return Traffic(jnp.asarray(br, jnp.float32), jnp.asarray(bw, jnp.float32),
                       jnp.asarray(2, jnp.int32),     # lock acquire precedes data
                       jnp.asarray(loads + upd, jnp.int32))

    def begin_update(self, state, slot, new_value, torn_words):
        half = state.data[slot].at[:torn_words].set(new_value[:torn_words])
        return state._replace(lock=state.lock.at[slot].set(jnp.uint32(1)),
                              data=state.data.at[slot].set(half))

    def check_invariants(self, spec, state):
        out = super().check_invariants(spec, state)
        out["lock_released"] = state.lock != 0      # no holder at rest
        return out


class _NodePool(_Versioned):
    """Shared base for INDIRECT / CACHED_WF: pool of n + 2p immutable nodes."""

    def init(self, n, k, p_max, data):
        # n installed nodes + 2p slack (SMR in-flight bound).
        m = n + 2 * p_max
        pool = jnp.zeros((m, k), WORD_DTYPE)
        pool = pool.at[:n].set(data)
        bptr = jnp.arange(n, dtype=jnp.int32)           # cell i -> node i
        free_ring = jnp.concatenate(
            [jnp.arange(n, m, dtype=jnp.int32),
             jnp.full((n,), NULL)])                     # slots occupied by live nodes
        mark = jnp.zeros((n,), bool) if self.name == "cached_wf" else _empty(bool)
        return TableState(data, jnp.zeros((n,), jnp.uint32), bptr, mark,
                          _empty(jnp.uint32), pool, free_ring,
                          jnp.uint32(0), jnp.uint32(0))

    def commit(self, state, new_data, new_version, n_updates, p):
        # One fresh node per dirty cell holds the final value; the old node is
        # retired to the ring.  (Intermediate values of a CAS chain live and
        # die inside the batch; they are counted in stats.n_updates.)
        n = state.version.shape[0]
        dirty = new_version != state.version
        d_count = jnp.sum(dirty.astype(jnp.uint32))
        order = jnp.argsort(~dirty, stable=True)   # dirty slots first
        dslots = jnp.where(jnp.arange(n) < d_count, order, n)
        max_d = min(n, p)
        dslots = dslots[:max_d]
        live = dslots < n
        new_nodes, st2 = ring_alloc(state, d_count, max_d)
        old_nodes = state.bptr[jnp.minimum(dslots, n - 1)]
        pool = st2.pool.at[jnp.where(live, new_nodes, st2.pool.shape[0])].set(
            new_data[jnp.minimum(dslots, n - 1)], mode="drop")
        bptr = st2.bptr.at[jnp.where(live, dslots, n)].set(
            jnp.where(live, new_nodes, NULL), mode="drop")
        st3 = st2._replace(pool=pool, bptr=bptr, data=new_data,
                           version=new_version)
        return ring_free(st3, jnp.where(live, old_nodes, NULL), d_count, max_d)

    def memory_bytes(self, n, k, p):
        w = WORD_BYTES
        pool = (n + 2 * p) * k * w + (n + 2 * p) * w    # pool + ring
        if self.name == "indirect":
            return n * w + pool                          # ptr + pool + ring
        return n * (k + 2) * w + pool


@register_strategy
class Indirect(_KernelLowering, _NodePool):
    name = "indirect"
    lock_free = True

    def logical(self, state):
        return state.pool[state.bptr]

    def engine_view(self, state):
        # `commit` writes new_data into the shadow alongside the node swing,
        # so the shadow always equals pool[bptr]; reading it saves the
        # dependent gather on every engine batch (reads never touch it).
        return state.data

    def read(self, state, slots):
        node = state.bptr[slots]
        return state.pool[node], jnp.ones((slots.shape[0],), bool)

    def traffic(self, stats, k, p):
        w = WORD_BYTES
        cell = k * w
        loads, upd, dirty = stats.n_loads, stats.n_updates, stats.n_dirty_cells
        br = loads * (w + cell) + upd * (w + cell)
        bw = upd * cell + dirty * w
        return Traffic(jnp.asarray(br, jnp.float32), jnp.asarray(bw, jnp.float32),
                       jnp.asarray(2, jnp.int32),       # ptr chase on EVERY load
                       jnp.asarray(upd, jnp.int32))

    def begin_update(self, state, slot, new_value, torn_words):
        # Node written; pointer swing (the linearization point) pending.
        free_slot, state = sim_alloc(state)
        pool = state.pool.at[free_slot].set(new_value)
        return state._replace(pool=pool)

    def check_invariants(self, spec, state):
        out = super().check_invariants(spec, state)
        m = state.pool.shape[0]
        bad_ptr = (state.bptr < 0) | (state.bptr >= m)
        node = state.pool[jnp.clip(state.bptr, 0, m - 1)]
        out["pointer_range"] = bad_ptr
        # commit maintains data as an exact shadow of pool[bptr]
        out["shadow_agrees"] = ~bad_ptr & jnp.any(node != state.data, axis=1)
        return out


class _Cached(_NodePool):
    """Shared traffic model for the two cached layouts (1-gather fast path)."""

    def traffic(self, stats, k, p):
        w = WORD_BYTES
        cell = k * w
        loads, raced, upd = stats.n_loads, stats.n_raced_loads, stats.n_updates
        fast = loads - raced
        br = fast * (cell + 2 * w) + raced * (cell + 2 * w + cell) + upd * (cell + 3 * w)
        bw = upd * (2 * cell + 3 * w)                   # node + cache + ver/ptr
        chains = jnp.where(raced > 0, 2, 1)             # fast path: ONE gather
        return Traffic(jnp.asarray(br, jnp.float32), jnp.asarray(bw, jnp.float32),
                       jnp.asarray(chains, jnp.int32),
                       jnp.asarray(2 * upd, jnp.int32))  # ptr CAS + ver lock


@register_strategy
class CachedWF(_KernelLowering, _Cached):
    name = "cached_wf"
    lock_free = True

    def commit(self, state, new_data, new_version, n_updates, p):
        new_state = super().commit(state, new_data, new_version, n_updates, p)
        # Batch completes cleanly: every dirty cell ends validated (unmarked)
        # with cache == backup.
        return new_state._replace(mark=jnp.zeros_like(state.mark))

    def read(self, state, slots):
        v1 = state.version[slots]
        val = state.data[slots]
        marked = state.mark[slots]
        v2 = state.version[slots]
        fastok = (~marked) & (v1 == v2) & (v1 % 2 == 0)
        backup = state.pool[state.bptr[slots]]          # slow path (protected)
        return (jnp.where(fastok[:, None], val, backup),
                jnp.ones((slots.shape[0],), bool))

    def begin_update(self, state, slot, new_value, torn_words):
        # Linearization point (pointer install) HAS happened: new node is the
        # truth; cache is mid-copy and marked invalid; version odd.
        half = state.data[slot].at[:torn_words].set(new_value[:torn_words])
        free_slot, state = sim_alloc(state)
        pool = state.pool.at[free_slot].set(new_value)
        return state._replace(
            pool=pool,
            bptr=state.bptr.at[slot].set(free_slot),
            mark=state.mark.at[slot].set(True),
            version=state.version.at[slot].add(jnp.uint32(1)),
            data=state.data.at[slot].set(half))

    def check_invariants(self, spec, state):
        out = super().check_invariants(spec, state)
        m = state.pool.shape[0]
        bad_ptr = (state.bptr < 0) | (state.bptr >= m)
        backup = state.pool[jnp.clip(state.bptr, 0, m - 1)]
        out["pointer_range"] = bad_ptr
        # every batch ends validated: cache == backup, marks clear
        out["cache_matches_backup"] = \
            ~bad_ptr & jnp.any(backup != state.data, axis=1)
        out["mark_clear"] = state.mark
        return out


@register_strategy
class CachedME(_KernelLowering, _Cached):
    name = "cached_me"
    lock_free = True

    def init(self, n, k, p_max, data):
        m = max(3 * p_max, 1)
        pool = jnp.zeros((m, k), WORD_DTYPE)
        bptr = jnp.full((n,), NULL)                     # null: cache is live
        free_ring = jnp.arange(m, dtype=jnp.int32)
        return TableState(data, jnp.zeros((n,), jnp.uint32), bptr,
                          mark=_empty(bool), lock=_empty(jnp.uint32),
                          pool=pool, free_ring=free_ring,
                          ring_head=jnp.uint32(0), alloc_gen=jnp.uint32(0))

    def commit(self, state, new_data, new_version, n_updates, p):
        # Transient backups: installed during the update, uninstalled after
        # the cache copy (backup returns to tagged null carrying the version).
        # Pool slots cycle through the 3p ring within the batch; the final
        # layout has all-null bptr (paper §3.2 invariant).
        dirty = new_version != state.version
        ring_cap = state.free_ring.shape[0]
        u_count = jnp.minimum(n_updates.astype(jnp.uint32),
                              jnp.uint32(ring_cap))
        max_u = min(p, ring_cap)
        slots_alloc, st2 = ring_alloc(state, u_count, max_u)
        # All transients are freed within the batch: push them straight back.
        st3 = ring_free(st2, slots_alloc, u_count, max_u)
        # Tagged null: encode low version bits so a stale CAS can't ABA.
        tag = (new_version >> 1).astype(jnp.int32) & jnp.int32(0x3FFFFFFF)
        bptr = jnp.where(dirty, -(tag + 2), st3.bptr)
        return st3._replace(data=new_data, version=new_version, bptr=bptr)

    def read(self, state, slots):
        v1 = state.version[slots]
        val = state.data[slots]
        bp = state.bptr[slots]
        is_null = bp < 0
        v2 = state.version[slots]
        fastok = is_null & (v1 == v2) & (v1 % 2 == 0)
        backup = state.pool[jnp.maximum(bp, 0)]         # slow path: live node
        # If bptr is a real node, the node holds the live value (invariant);
        # either way the reader makes progress -> ok is always True.
        return (jnp.where(fastok[:, None], val, backup),
                jnp.ones((slots.shape[0],), bool))

    def begin_update(self, state, slot, new_value, torn_words):
        half = state.data[slot].at[:torn_words].set(new_value[:torn_words])
        free_slot, state = sim_alloc(state)
        pool = state.pool.at[free_slot].set(new_value)
        return state._replace(
            pool=pool,
            bptr=state.bptr.at[slot].set(free_slot),
            version=state.version.at[slot].add(jnp.uint32(1)),
            data=state.data.at[slot].set(half))

    def memory_bytes(self, n, k, p):
        w = WORD_BYTES
        return n * (k + 2) * w + 3 * p * k * w + 3 * p * w

    def check_invariants(self, spec, state):
        out = super().check_invariants(spec, state)
        # At rest every bptr is null (paper §3.2): either the init/restore
        # NULL or the tagged null commit leaves, whose tag must agree with
        # the cell's version (-(tag+2) with tag = (ver >> 1) & 0x3FFFFFFF).
        tag = (state.version >> 1).astype(jnp.int32) & jnp.int32(0x3FFFFFFF)
        ok = (state.bptr == NULL) | (state.bptr == -(tag + 2))
        out["tagged_null"] = ~ok
        return out
