"""Per-slot bounded version lists over big atomics (DESIGN.md §7).

The paper's §2 names version lists as a headline application: "allows the
first version, most commonly accessed, to be stored inline and updated
atomically".  This module is that application done properly ON the engine:

  head cells   The newest version of every slot lives INLINE in a
               `cellw = k + 2` word big-atomic cell — [value(k), ts, prev]
               — of an ordinary `AtomicSpec` table.  Publishing is ONE
               engine STORE batch (`atomics.apply` semantics), so value,
               timestamp and chain pointer can never tear apart, every
               registered strategy (and plug-ins) gets version lists for
               free, and the head's cell version gives readers the usual
               even/odd torn-write detection.
  node pool    Older versions sit in a per-slot ring of `depth - 1`
               immutable pool nodes (`pool[n, depth-1, k+2]`).  A publish
               copies the displaced head into its ring position
               (`count % (depth-1)`) and links the new head to it; a node
               is overwritten only after depth-1 further publishes of its
               slot, so every chain is bounded to the `depth` newest
               versions.

`snapshot_read(spec, state, slots, ts)` returns, per queried slot, the
newest version with timestamp <= ts — a TIMESTAMPED snapshot of an
arbitrary slot set, consistent by construction (the walk runs against one
immutable state pytree; concurrency is cross-batch).  Reads past the
retained window are reported honestly (`ok=False`, lap detection via the
strict timestamp-decrease invariant of a healthy chain), never silently
wrong.  `core.multiversion` is rewired on top of this module.

Timestamps are caller-supplied uint32 and must be strictly increasing per
slot (e.g. a training step or a global publish counter); `publish` does not
reorder history.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.layout import WORD_DTYPE
from repro.core.specs import VersionSpec

NULLV = jnp.uint32(0xFFFFFFFF)     # "no older version" chain terminator


class VersionState(NamedTuple):
    """Pure pytree: head table + version-node pool + per-slot publish count.

    table: TableState of the `spec.head_spec()` big-atomic head cells
    pool:  word[n, depth-1, k+2] per-slot ring of displaced versions
    count: uint32[n] publishes per slot (ring cursor + version index)
    """

    table: object
    pool: jax.Array
    count: jax.Array


def init(spec: VersionSpec, initial=None, ts0: int = 0) -> VersionState:
    """Every slot starts with one inline version (`initial` values, ts=ts0)
    and an empty chain."""
    vals = (np.zeros((spec.n, spec.k), np.uint32) if initial is None
            else np.asarray(initial, np.uint32))
    if vals.shape != (spec.n, spec.k):
        raise ValueError(f"initial shape {vals.shape} != "
                         f"({spec.n}, {spec.k})")
    cells = np.zeros((spec.n, spec.cellw), np.uint32)
    cells[:, :spec.k] = vals
    cells[:, spec.k] = np.uint32(ts0)
    cells[:, spec.k + 1] = np.uint32(0xFFFFFFFF)        # NULLV
    table = engine.init(spec.head_spec(), cells)
    pool = jnp.zeros((spec.n, spec.ring_depth, spec.cellw), WORD_DTYPE)
    return VersionState(table, pool, jnp.zeros((spec.n,), jnp.uint32))


@functools.partial(jax.jit, static_argnames=("spec",))
def _publish(spec: VersionSpec, state: VersionState, slots, values, ts):
    n, k, rd = spec.n, spec.k, spec.ring_depth
    slots = jnp.asarray(slots, jnp.int32)
    values = jnp.asarray(values, WORD_DTYPE)
    ts = jnp.asarray(ts, jnp.uint32)
    q = slots.shape[0]
    # The displaced head's ring position and global chain pointer.
    pos = (state.count[slots] % jnp.uint32(rd)).astype(jnp.int32)
    prev = (slots.astype(jnp.uint32) * jnp.uint32(rd)
            + pos.astype(jnp.uint32))
    new_cells = jnp.concatenate(
        [values, ts[:, None], prev[:, None]], axis=1)
    # ONE engine STORE batch: installs the new head atomically AND returns
    # the displaced head cell (STORE's witnessed pre-value).
    ops = engine.stores(slots, new_cells, k=spec.cellw)
    table, _, res, _, _ = engine.apply(spec.head_spec(), state.table, ops)
    pool = state.pool.at[slots, pos].set(res.value)
    count = state.count.at[slots].add(jnp.uint32(1))
    del n, q
    return VersionState(table, pool, count)


def publish(spec: VersionSpec, state: VersionState, slots, values, ts
            ) -> VersionState:
    """Install a new version (value, ts) at each of `slots` — one engine
    STORE batch; the displaced heads move into the per-slot pool rings.

    Slots must be distinct within one batch (checked on concrete input)
    and `ts` strictly greater than each slot's current head timestamp
    (caller contract; history is never reordered)."""
    try:
        s_np = np.asarray(slots)
    except jax.errors.TracerArrayConversionError:
        s_np = None
    if s_np is not None and len(np.unique(s_np)) != len(s_np):
        raise ValueError(f"publish slots must be distinct within one "
                         f"batch: {sorted(np.asarray(s_np).tolist())}")
    return _publish(spec, state, slots, values, ts)


@functools.partial(jax.jit, static_argnames=("spec",))
def snapshot_read(spec: VersionSpec, state: VersionState, slots, ts):
    """Timestamped snapshot of an arbitrary slot set.

    Per queried slot: the value + timestamp of the newest version with
    version-ts <= ts[i].  ok=False when the head cell is torn (blocking
    strategies only) or the requested time predates the bounded chain
    (version evicted — honesty, not silence).

    Returns (values[q, k], found_ts[q], ok[q]).
    """
    n, k, rd = spec.n, spec.k, spec.ring_depth
    slots = jnp.asarray(slots, jnp.int32)
    ts = jnp.asarray(ts, jnp.uint32)
    q = slots.shape[0]
    heads, hok = engine.read(spec.head_spec(), state.table, slots)
    hval, hts, hprev = heads[:, :k], heads[:, k], heads[:, k + 1]

    flat = state.pool.reshape(n * rd, spec.cellw)
    values = jnp.where((hts <= ts)[:, None], hval,
                       jnp.zeros((q, k), WORD_DTYPE))
    found_ts = jnp.where(hts <= ts, hts, jnp.uint32(0))
    found = hts <= ts
    cur = jnp.where(found, NULLV, hprev)       # walk only unresolved lanes
    prev_ts = hts
    for _ in range(rd):
        is_node = cur != NULLV
        node = flat[jnp.where(is_node, cur, 0).astype(jnp.int32)]
        nts = node[:, k]
        # A healthy chain strictly decreases in ts; a recycled ring slot
        # holds a NEWER version and breaks the invariant => lap detected.
        valid = is_node & (nts < prev_ts)
        hit = valid & (nts <= ts)
        values = jnp.where(hit[:, None], node[:, :k], values)
        found_ts = jnp.where(hit, nts, found_ts)
        found = found | hit
        cur = jnp.where(valid & ~hit, node[:, k + 1], NULLV)
        prev_ts = jnp.where(valid, nts, prev_ts)
    return values, found_ts, hok & found


def latest(spec: VersionSpec, state: VersionState, slots):
    """Newest version of each slot: (values[q, k], ts[q], ok[q])."""
    heads, hok = engine.read(spec.head_spec(), state.table,
                             jnp.asarray(slots, jnp.int32))
    return heads[:, :spec.k], heads[:, spec.k], hok


def history(spec: VersionSpec, state: VersionState, slot: int) -> list:
    """Host-side debug/test helper: the retained (ts, value) chain of one
    slot, newest first (walks exactly like `snapshot_read`)."""
    head = np.asarray(engine.logical(spec.head_spec(), state.table))[slot]
    flat = np.asarray(state.pool).reshape(spec.n * spec.ring_depth,
                                          spec.cellw)
    k = spec.k
    out = [(int(head[k]), head[:k].copy())]
    cur, prev_ts = head[k + 1], head[k]
    for _ in range(spec.ring_depth):
        if cur == np.uint32(0xFFFFFFFF):
            break
        node = flat[int(cur)]
        if not node[k] < prev_ts:
            break                               # lapped (recycled ring slot)
        out.append((int(node[k]), node[:k].copy()))
        cur, prev_ts = node[k + 1], node[k]
    return out
