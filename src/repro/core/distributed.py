"""Mesh-sharded big atomics on the v2 spec/registry engine (DESIGN.md §6).

The paper's experiments stop at one node; this module is the scale-out
execution layer for everything the unified engine can express.  A structure's
n cells shard over one mesh axis; each device owns a contiguous block of
cells (or the `slot % n_shards` residue class with `interleave=True`) plus
its own `p_local`-lane slice of the op batch.  One collective round-trip
executes a globally linearizable batch over the FULL op schema:

  1. route   — each device buckets its ops by owner shard and exchanges them
               with a fixed-capacity `all_to_all` (capacity = `cap` per
               (src, dst) pair).  LL/SC/VALIDATE lanes ride with their link
               version and a link-matches-slot bit, so the owner shard can
               arbitrate links it has never seen (the routed per-owner
               `LinkCtx`).  Lanes beyond capacity are NOT silently dropped:
               they surface in the returned per-lane `overflow` mask with
               `success=False` and leave the table untouched.
  2. apply   — every shard runs the engine's round on the ops it owns:
               `engine.round_for` (the tier predicate and the strategy's
               fast and slow kernels, or `engine.linearize` where the
               layout lowers none) and the strategy's `commit`, resolved
               through the registry, so all registered layouts — built-in
               or test-registered — run sharded unchanged.  Linearization
               order is (owner, src device, lane) — a fixed total order,
               so the result equals a global sequential application in
               that order (`linearization_order` emits it for the oracle
               harness).
  3. return  — results (and, for LL lanes, the linked version) ride the
               inverse `all_to_all` back to the issuing lane, which merges
               them into its persistent per-lane `LinkCtx`.

The state never leaves its shard: `init_dist` builds each shard on its own
device (from a function of global cell ids, or an array placed shard by
shard), `apply(..., donate=True)` updates it in place, and `gather` /
`count_cells` read it back shard by shard.  Route and return run under the
device scopes `dist.route` and `dist.return`, siblings of the round's
`engine.*` scopes; `apply` records the host spans `atomics.dist.apply`,
`.validate` and `.launch`.

`apply_hash` runs the same round for a `HashSpec` CacheHash: ops route by
key owner (`bucket // nb_local`, the top bits of the bucket hash) and every
shard applies its slice with `cachehash.apply_hash` over its own node pool.

Collective cost per batch and device: 2 all_to_alls moving
`n_shards * cap * (3k + 6)` words (table) / `n_shards * cap * (2vw + 4)`
words (hash) — the roofline cell `benchmarks/bench_distributed.py` sweeps;
`collective_words` is the exact model.

The v1 surface (`ShardedTable` / `init_sharded` / `make_apply` /
`reference_apply`, load/store/CAS only, PLAIN layout) survives as
deprecation shims over this engine.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cachehash as ch
from repro.core import engine
from repro.core import registry
from repro.core.deprecation import warn_once
from repro.core.layout import TableState, WORD_DTYPE
from repro.core.specs import AtomicSpec, HashSpec
from repro.kernels import engine_round
from repro.obs import telemetry as obs_telemetry

# Names in the profiler's trace.  Host spans of `apply`:
SPAN_APPLY = "atomics.dist.apply"
SPAN_VALIDATE = "atomics.dist.apply.validate"   # check_kinds, batch width
SPAN_LAUNCH = "atomics.dist.apply.launch"       # pad, ctx, jit call
# Device scopes of the sharded round, siblings of the round's `engine.*`
# scopes (never around them):
SCOPE_ROUTE = "dist.route"     # owner and rank, packing, outbound all_to_alls
SCOPE_RETURN = "dist.return"   # inbound all_to_alls, merge into the source


@dataclasses.dataclass(frozen=True)
class DistSpec:
    """Static shape of a sharded structure: an inner spec + mesh geometry.

    inner:          the structure being sharded (`AtomicSpec` or `HashSpec`);
                    its strategy resolves through the registry per shard.
    axis:           mesh axis name the cells and lanes shard over.
    n_shards:       devices along `axis` (cells split n / n_shards each).
    p_local:        op lanes issued per device; p_global = n_shards * p_local.
    route_capacity: per-(src, dst) slots in the all_to_all buffers (default
                    p_local, which can never overflow ops a device issues).
                    The collective bytes are EXACTLY proportional to this.
    dedup_loads:    loads of one cell from one source device whose cell sees
                    only loads from that source route ONCE; duplicates are
                    filled locally from the representative (safe: the order
                    is source-major, such loads are adjacent).
    interleave:     owner = slot % n_shards instead of contiguous blocks
                    (tables only; spreads contiguous-slot hotspots).
    n_nodes:        > 1 factors the shard axis as (n_nodes, devs_per_node)
                    and routes HIERARCHICALLY (tables only): phase 1 is an
                    intra-node all_to_all over `axis` that combines each
                    node's lanes onto the relay device whose in-node index
                    matches the owner's, phase 2 is ONE cross-node
                    all_to_all over `node_axis`.  Cross-node words drop from
                    n_shards*cap to n_nodes*node_capacity per device — the
                    cross-node combining win the executor overlaps rounds
                    behind (DESIGN.md §9).
    node_axis:      mesh axis of size n_nodes the cross-node hop runs over.
    node_capacity:  per-(relay, dst-node) slots in the phase-2 buffers
                    (default devs_per_node * cap, which can never overflow).
    """

    inner: Any                       # AtomicSpec | HashSpec
    axis: str = "shard"
    n_shards: int = 1
    p_local: int = 64
    route_capacity: int | None = None
    dedup_loads: bool = False
    interleave: bool = False
    n_nodes: int = 1
    node_axis: str = "node"
    node_capacity: int | None = None

    def __post_init__(self):
        if self.n_shards <= 0 or self.p_local <= 0:
            raise ValueError(f"mesh geometry must be positive: {self}")
        if self.n_nodes <= 0:
            raise ValueError("n_nodes must be positive")
        if self.n_nodes > 1:
            if isinstance(self.inner, HashSpec):
                raise ValueError("hierarchical routing applies to tables "
                                 "only (hash ops route flat)")
            if self.n_shards % self.n_nodes:
                raise ValueError(f"n_shards={self.n_shards} not divisible "
                                 f"by n_nodes={self.n_nodes}")
        if self.node_capacity is not None and self.node_capacity <= 0:
            raise ValueError("node_capacity must be positive")
        if isinstance(self.inner, HashSpec):
            if self.interleave:
                raise ValueError("interleave applies to tables only (hash "
                                 "buckets route by hash top bits)")
            if self.dedup_loads:
                raise ValueError("dedup_loads applies to tables only (hash "
                                 "FINDs are not dedup'd)")
            if self.inner.nb % self.n_shards:
                raise ValueError(f"nb={self.inner.nb} not divisible by "
                                 f"n_shards={self.n_shards}")
        elif isinstance(self.inner, AtomicSpec):
            if self.inner.n % self.n_shards:
                raise ValueError(f"n={self.inner.n} not divisible by "
                                 f"n_shards={self.n_shards}")
        else:
            raise TypeError(f"inner must be AtomicSpec or HashSpec: "
                            f"{type(self.inner)}")
        if self.route_capacity is not None and self.route_capacity <= 0:
            raise ValueError("route_capacity must be positive")

    # -- derived geometry ----------------------------------------------------

    @property
    def is_hash(self) -> bool:
        return isinstance(self.inner, HashSpec)

    @property
    def n_global(self) -> int:
        return self.inner.nb if self.is_hash else self.inner.n

    @property
    def n_local(self) -> int:
        return self.n_global // self.n_shards

    @property
    def p_global(self) -> int:
        return self.n_shards * self.p_local

    @property
    def cap(self) -> int:
        return self.route_capacity or self.p_local

    @property
    def devs_per_node(self) -> int:
        return self.n_shards // self.n_nodes

    @property
    def cap2(self) -> int:
        """Phase-2 per-(relay, dst-node) capacity (hierarchical only)."""
        return self.node_capacity or self.devs_per_node * self.cap

    def local_spec(self):
        """The per-shard spec the local engine runs (same strategy name, so
        the registry resolves the same `StrategyImpl` on every shard)."""
        if self.is_hash:
            return dataclasses.replace(self.inner, nb=self.n_local)
        return dataclasses.replace(self.inner, n=self.n_local)


class DistState(NamedTuple):
    """Pure pytree: the per-shard local states stacked on a leading
    [n_shards] axis (every leaf), sharded `P(axis)` over the mesh.  The
    local states are whatever the strategy's `init` builds (`TableState`)
    or `cachehash.init_hash` builds (`HashState`) — the distribution layer
    never looks inside them."""

    local: Any


def _unstack(state):
    """Inside shard_map: leading [1] shard axis -> the local pytree."""
    return jax.tree_util.tree_map(lambda x: x[0], state)


def _restack(state):
    """Inside shard_map: the local pytree -> leading [1] shard axis.  The
    barrier keeps XLA from moving the reshape into the round's branches,
    where each branch would then hold the table's 1-D fields twice.  On a
    four-chip mesh XLA then copies the donated `data` whole ahead of the
    round's branches (ROADMAP A6)."""
    return jax.tree_util.tree_map(lambda x: x[None],
                                  lax.optimization_barrier(state))


def _mesh_shards(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


def _pspec(dspec: DistSpec) -> P:
    """Shard-axis partition spec: hierarchical specs split the stacked
    [n_shards] leading dim over (node_axis, axis) — shard o lives on mesh
    coordinate (o // devs_per_node, o % devs_per_node)."""
    if dspec.n_nodes > 1:
        return P((dspec.node_axis, dspec.axis))
    return P(dspec.axis)


def _check_mesh(mesh: Mesh, dspec: DistSpec) -> None:
    if dspec.n_nodes > 1:
        got = (_mesh_shards(mesh, dspec.node_axis),
               _mesh_shards(mesh, dspec.axis))
        want = (dspec.n_nodes, dspec.devs_per_node)
        if got != want:
            raise ValueError(f"mesh axes ({dspec.node_axis!r}, "
                             f"{dspec.axis!r}) have {got} devices, spec "
                             f"says {want}")
    elif _mesh_shards(mesh, dspec.axis) != dspec.n_shards:
        raise ValueError(f"mesh axis {dspec.axis!r} has "
                         f"{_mesh_shards(mesh, dspec.axis)} devices, spec "
                         f"says {dspec.n_shards}")


def _shard_index(dspec: DistSpec):
    """Inside shard_map: this device's shard (o = node * devs_per_node +
    in-node index when hierarchical, as `_pspec` places it)."""
    if dspec.n_nodes > 1:
        return (lax.axis_index(dspec.node_axis) * dspec.devs_per_node
                + lax.axis_index(dspec.axis))
    return lax.axis_index(dspec.axis)


def _local_cells(dspec: DistSpec):
    """Inside shard_map: the global ids of this shard's cells, in local
    order (int32[n_local])."""
    i = _shard_index(dspec).astype(jnp.int32)
    local = jnp.arange(dspec.n_local, dtype=jnp.int32)
    if dspec.interleave:
        return local * dspec.n_shards + i
    return i * dspec.n_local + local


def init_dist(mesh: Mesh, dspec: DistSpec, initial=None) -> DistState:
    """Build the sharded initial state: one local state per shard, stacked
    on a leading [n_shards] axis placed `P(axis)` on the mesh
    (`P((node_axis, axis))` when hierarchical).  Each shard is built on its
    own device by one jitted shard_map, so no device ever holds more than
    its shard.

    `initial` (tables only) gives the initial GLOBAL logical values: a
    function of global cell ids (int32[m], traced) -> word[m, k], which
    each shard evaluates on its own cells, or a word[n, k] array, placed
    shard by shard.  None starts every cell at zero."""
    _check_mesh(mesh, dspec)
    lsp = dspec.local_spec()
    spec = _pspec(dspec)
    args = ()
    if dspec.is_hash:
        if initial is not None:
            raise ValueError("hash tables initialize empty; insert instead")

        def local():
            return _restack(ch.init_hash(lsp))
    elif initial is None or callable(initial):
        def local():
            words = None if initial is None else initial(
                _local_cells(dspec))
            return _restack(engine.init(lsp, words))
    else:
        initial = np.asarray(initial)
        if initial.shape != (dspec.n_global, lsp.k):
            raise ValueError(f"initial shape {initial.shape} != "
                             f"({dspec.n_global}, {lsp.k})")
        s, n_local = dspec.n_shards, dspec.n_local
        stacked = (initial.reshape(n_local, s, lsp.k).swapaxes(0, 1)
                   if dspec.interleave else
                   initial.reshape(s, n_local, lsp.k))
        args = (jax.device_put(stacked, NamedSharding(mesh, spec)),)

        def local(words):
            return _restack(engine.init(lsp, words[0]))
    mapped = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * len(args),
                           out_specs=spec, check_vma=False)
    return DistState(jax.jit(mapped)(*args))


def init_dist_ctx(mesh: Mesh, dspec: DistSpec) -> engine.LinkCtx:
    """A fresh p_global-lane LinkCtx, sharded by source lane."""
    ctx = engine.init_ctx(dspec.p_global, dspec.inner.k)
    return jax.device_put(ctx, NamedSharding(mesh, _pspec(dspec)))


# ---------------------------------------------------------------------------
# The route -> apply -> return round (tables: full LOAD/STORE/CAS/LL/SC/
# VALIDATE schema with a routed per-owner LinkCtx).
# ---------------------------------------------------------------------------

def _owner_and_local(dspec: DistSpec, slot):
    """Owner shard + local cell index of each (table) global slot."""
    s = dspec.n_shards
    if dspec.interleave:
        return slot % s, slot // s
    return jnp.clip(slot // dspec.n_local, 0, s - 1), slot % dspec.n_local


def _dst_ranks(owner, cap: int, s: int, p: int):
    """Rank of each lane within its (src, dst) bucket + the fits mask."""
    order = jnp.argsort(owner, stable=True)
    inv = jnp.argsort(order, stable=True)
    s_owner = owner[order]
    idx = jnp.arange(p, dtype=jnp.int32)
    seg_start = jnp.concatenate([jnp.ones((1,), bool),
                                 s_owner[1:] != s_owner[:-1]])
    start = engine._segmented_scan_max(jnp.where(seg_start, idx, -1),
                                       seg_start)
    rank = (idx - start)[inv]
    fits = (rank < cap) & (owner < s)
    return rank, fits


def _packer(dst, size: int):
    """Masked scatter into flat [size] send buffers (`dst == size` drops)."""
    def pack(x, fill):
        buf = jnp.full((size,) + x.shape[1:], fill, x.dtype)
        return buf.at[dst].set(x, mode="drop")
    return pack


def _a2a(axis: str, s: int, cap: int):
    def go(x):
        return lax.all_to_all(x.reshape((s, cap) + x.shape[1:]), axis,
                              split_axis=0, concat_axis=0, tiled=False)
    return go


def _dedup(kind, slot, n: int, p: int):
    """Source-side load dedup: in each same-slot group whose active lanes
    are ALL loads, every load after the first becomes IDLE and inherits the
    first lane's routed answer.  Returns (kind', rep[p])."""
    lane = jnp.arange(p, dtype=jnp.int32)
    active = kind != engine.IDLE
    key = jnp.where(active, slot, n)              # idle lanes group apart
    d_order = jnp.argsort(key, stable=True)
    d_inv = jnp.argsort(d_order, stable=True)
    ds = key[d_order]
    dk = kind[d_order]
    d_start = jnp.concatenate([jnp.ones((1,), bool), ds[1:] != ds[:-1]])
    seg_end = jnp.concatenate([d_start[1:], jnp.ones((1,), bool)])
    start_idx = engine._segmented_scan_max(jnp.where(d_start, lane, -1),
                                           d_start)
    nonload = (dk != engine.LOAD) & (ds < n)
    # Suffix-any per segment, gathered at the segment START = full-segment
    # any (the suffix scan alone would miss non-loads BEFORE a lane).
    any_nonload = engine._seg_broadcast_any(nonload, seg_end)[start_idx]
    dup = (dk == engine.LOAD) & (ds < n) & ~any_nonload & ~d_start
    rep = jnp.where(dup, d_order[start_idx], d_order)[d_inv]
    return jnp.where(rep != lane, engine.IDLE, kind), rep


def _owner_round(lsp: AtomicSpec, mode: str, st, kind, slot, lver, lok,
                 expected, desired):
    """The engine's round on the ops a shard received, against a routed
    per-owner LinkCtx, then the strategy's commit.  Returns (state',
    ApplyResult, the lanes' linked versions)."""
    impl = registry.get_strategy(lsp.strategy)
    m = kind.shape[0]
    octx = engine.LinkCtx(slot=jnp.where(lok, slot, -1), version=lver,
                          value=jnp.zeros((m, lsp.k), WORD_DTYPE),
                          linked=lok)
    rops = engine.OpBatch(kind, slot, expected, desired)
    new_data, new_ver, new_octx, res, stats = engine.round_for(
        lsp, impl, mode)(impl.engine_view(st), st.version, octx, rops)
    with jax.named_scope(engine.SCOPE_COMMIT):
        st = impl.commit(st, new_data, new_ver, stats.n_updates, m)
    return st, res, new_octx.version


def _merge_ctx(ctx: engine.LinkCtx, kind, slot, executed, value, ret_ver
               ) -> engine.LinkCtx:
    """The source's persistent ctx after its lanes' routed answers: LLs
    that executed link, SCs that executed consume the link (a dropped SC
    keeps it)."""
    is_ll = executed & (kind == engine.LL)
    is_sc = executed & (kind == engine.SC)
    return engine.LinkCtx(
        slot=jnp.where(is_ll, slot, ctx.slot),
        version=jnp.where(is_ll, ret_ver, ctx.version),
        value=jnp.where(is_ll[:, None], value, ctx.value),
        linked=jnp.where(is_ll, True, jnp.where(is_sc, False, ctx.linked)))


def _jit_round(mesh: Mesh, local_fn, spec: P, donate: bool):
    mapped = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec,) * 6,
                           out_specs=(spec,) * 5, check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=256)
def _build_table_apply(mesh: Mesh, dspec: DistSpec, mode: str,
                       donate: bool = False):
    s, cap, axis = dspec.n_shards, dspec.cap, dspec.axis
    lsp: AtomicSpec = dspec.local_spec()
    p_local, k = dspec.p_local, lsp.k

    def local_fn(state, ctx, kind, slot, expected, desired):
        if ctx is None:
            ctx = engine.init_ctx(p_local, k)
        lane = jnp.arange(p_local, dtype=jnp.int32)
        active0 = kind != engine.IDLE
        go = _a2a(axis, s, cap)

        # -- route out: ops + the link info the owner needs to arbitrate ----
        with jax.named_scope(SCOPE_ROUTE):
            rep = lane
            if dspec.dedup_loads:
                kind, rep = _dedup(kind, slot, dspec.n_global, p_local)
            active = kind != engine.IDLE
            owner, lslot = _owner_and_local(dspec, slot)
            owner = jnp.where(active, owner, s)
            rank, fits = _dst_ranks(owner, cap, s, p_local)
            link_ok = ctx.linked & (ctx.slot == slot)  # global-slot compare
            dst = jnp.where(fits, owner * cap + rank, s * cap)
            pack = _packer(dst, s * cap)
            r_kind = go(pack(jnp.where(fits, kind, engine.IDLE),
                             engine.IDLE)).reshape(s * cap)
            r_slot = go(pack(lslot, 0)).reshape(s * cap)
            r_exp = go(pack(expected, 0)).reshape(s * cap, k)
            r_des = go(pack(desired, 0)).reshape(s * cap, k)
            r_lver = go(pack(ctx.version, 0)).reshape(s * cap)
            r_lok = go(pack(link_ok, False)).reshape(s * cap)

        # -- apply: the engine's round at the owner --------------------------
        st, res, lver = _owner_round(lsp, mode, _unstack(state), r_kind,
                                     r_slot, r_lver, r_lok, r_exp, r_des)

        # -- route back: values, success, and the LL-linked version ---------
        with jax.named_scope(SCOPE_RETURN):
            b_val = go(res.value).reshape(s, cap, k)
            b_suc = go(res.success).reshape(s, cap)
            b_ver = go(lver).reshape(s, cap)
            safe_owner = jnp.clip(owner, 0, s - 1)
            safe_pos = jnp.maximum(jnp.where(fits, rank, -1), 0)
            value = jnp.where(fits[:, None], b_val[safe_owner, safe_pos], 0)
            success = jnp.where(fits, b_suc[safe_owner, safe_pos], False)
            ret_ver = b_ver[safe_owner, safe_pos]
            value = value[rep]
            success = success[rep]
            overflow = active0 & ~fits[rep]
            nctx = _merge_ctx(ctx, kind, slot, fits, value, ret_ver)
        return _restack(st), nctx, value, success, overflow

    return _jit_round(mesh, local_fn, P(axis), donate)


@functools.lru_cache(maxsize=256)
def _build_table_apply_2level(mesh: Mesh, dspec: DistSpec, mode: str,
                              donate: bool = False):
    """Hierarchical route -> apply -> return: intra-node combine onto the
    relay device whose in-node index matches the owner's, then ONE
    cross-node all_to_all (DESIGN.md §9).

    The owner device of shard o = o_node * d + o_dev sits at mesh
    coordinate (o_node, o_dev); phase 1 (over `axis`, within each node)
    moves every lane to the local device with index o_dev, phase 2 (over
    `node_axis`) moves it to the owner node — the in-node index is
    preserved across the node hop, so it lands exactly on the owner.
    Owner-side lane order is [src_node, phase-2 rank], and phase-2 ranks
    follow relay-lane order [src_dev, phase-1 rank]: the claimed total
    order is (owner, src node, src dev, lane) — `linearization_order`
    mirrors it host-side.  Capacity rejects at EITHER hop surface in the
    returned per-lane overflow mask; rejected lanes never reach a table.
    """
    axis, node_axis = dspec.axis, dspec.node_axis
    nn, d = dspec.n_nodes, dspec.devs_per_node
    cap1, cap2 = dspec.cap, dspec.cap2
    lsp: AtomicSpec = dspec.local_spec()
    p_local, k = dspec.p_local, lsp.k

    def local_fn(state, ctx, kind, slot, expected, desired):
        if ctx is None:
            ctx = engine.init_ctx(p_local, k)
        active0 = kind != engine.IDLE
        go1 = _a2a(axis, d, cap1)
        go2 = _a2a(node_axis, nn, cap2)

        with jax.named_scope(SCOPE_ROUTE):
            rep = jnp.arange(p_local, dtype=jnp.int32)
            if dspec.dedup_loads:
                kind, rep = _dedup(kind, slot, dspec.n_global, p_local)
            active = kind != engine.IDLE
            owner, lslot = _owner_and_local(dspec, slot)
            o_node = jnp.where(active, owner // d, nn)
            o_dev = jnp.where(active, owner % d, d)

            # -- phase 1 out: intra-node combine onto the o_dev relay -------
            link_ok = ctx.linked & (ctx.slot == slot)
            rank1, fits1 = _dst_ranks(o_dev, cap1, d, p_local)
            dst1 = jnp.where(fits1, o_dev * cap1 + rank1, d * cap1)
            pack1 = _packer(dst1, d * cap1)
            r1_kind = go1(pack1(jnp.where(fits1, kind, engine.IDLE),
                                engine.IDLE)).reshape(d * cap1)
            r1_slot = go1(pack1(lslot, 0)).reshape(d * cap1)
            r1_node = go1(pack1(o_node, nn)).reshape(d * cap1)
            r1_exp = go1(pack1(expected, 0)).reshape(d * cap1, k)
            r1_des = go1(pack1(desired, 0)).reshape(d * cap1, k)
            r1_lver = go1(pack1(ctx.version, 0)).reshape(d * cap1)
            r1_lok = go1(pack1(link_ok, False)).reshape(d * cap1)

            # -- phase 2 out: ONE cross-node hop to the owner node ----------
            key2 = jnp.where(r1_kind != engine.IDLE, r1_node, nn)
            rank2, fits2 = _dst_ranks(key2, cap2, nn, d * cap1)
            dst2 = jnp.where(fits2, key2 * cap2 + rank2, nn * cap2)
            pack2 = _packer(dst2, nn * cap2)
            r2_kind = go2(pack2(jnp.where(fits2, r1_kind, engine.IDLE),
                                engine.IDLE)).reshape(nn * cap2)
            r2_slot = go2(pack2(r1_slot, 0)).reshape(nn * cap2)
            r2_exp = go2(pack2(r1_exp, 0)).reshape(nn * cap2, k)
            r2_des = go2(pack2(r1_des, 0)).reshape(nn * cap2, k)
            r2_lver = go2(pack2(r1_lver, 0)).reshape(nn * cap2)
            r2_lok = go2(pack2(r1_lok, False)).reshape(nn * cap2)

        # -- apply at the owner (the same round as the flat path) -----------
        st, res, lver = _owner_round(lsp, mode, _unstack(state), r2_kind,
                                     r2_slot, r2_lver, r2_lok, r2_exp,
                                     r2_des)

        with jax.named_scope(SCOPE_RETURN):
            # -- return hop 2: owner node -> relay ---------------------------
            b2_val = go2(res.value).reshape(nn, cap2, k)
            b2_suc = go2(res.success).reshape(nn, cap2)
            b2_ver = go2(lver).reshape(nn, cap2)
            safe_n = jnp.clip(key2, 0, nn - 1)
            safe_r2 = jnp.maximum(jnp.where(fits2, rank2, -1), 0)
            v1 = jnp.where(fits2[:, None], b2_val[safe_n, safe_r2], 0)
            s1 = jnp.where(fits2, b2_suc[safe_n, safe_r2], False)
            ver1 = b2_ver[safe_n, safe_r2]

            # -- return hop 1: relay -> source (the fits2 bit rides back so
            #    the source learns which lanes ACTUALLY executed) -----------
            b1_val = go1(v1).reshape(d, cap1, k)
            b1_suc = go1(s1).reshape(d, cap1)
            b1_ver = go1(ver1).reshape(d, cap1)
            b1_exe = go1(fits2).reshape(d, cap1)
            safe_dev = jnp.clip(o_dev, 0, d - 1)
            safe_r1 = jnp.maximum(jnp.where(fits1, rank1, -1), 0)
            executed = fits1 & b1_exe[safe_dev, safe_r1]
            value = jnp.where(executed[:, None], b1_val[safe_dev, safe_r1],
                              0)
            success = jnp.where(executed, b1_suc[safe_dev, safe_r1], False)
            ret_ver = b1_ver[safe_dev, safe_r1]
            value = value[rep]
            success = success[rep]
            overflow = active0 & ~executed[rep]
            nctx = _merge_ctx(ctx, kind, slot, executed, value, ret_ver)
        return _restack(st), nctx, value, success, overflow

    return _jit_round(mesh, local_fn, P((node_axis, axis)), donate)


def _pad_ops(ops: engine.OpBatch, p: int) -> engine.OpBatch:
    """IDLE-pad the lane axis up to p (callers may issue fewer lanes)."""
    q = ops.kind.shape[0]
    if q == p:
        return ops
    pad, k = p - q, ops.desired.shape[1]
    return engine.OpBatch(
        jnp.concatenate([jnp.asarray(ops.kind, jnp.int32),
                         jnp.full((pad,), engine.IDLE, jnp.int32)]),
        jnp.concatenate([jnp.asarray(ops.slot, jnp.int32),
                         jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([jnp.asarray(ops.expected, WORD_DTYPE),
                         jnp.zeros((pad, k), WORD_DTYPE)]),
        jnp.concatenate([jnp.asarray(ops.desired, WORD_DTYPE),
                         jnp.zeros((pad, k), WORD_DTYPE)]))


def _pad_ctx(ctx: engine.LinkCtx, p: int, k: int) -> engine.LinkCtx:
    q = ctx.slot.shape[0]
    if q == p:
        return ctx
    blank = engine.init_ctx(p - q, k)
    return engine.LinkCtx(*[jnp.concatenate([a, b])
                            for a, b in zip(ctx, blank)])


def _check_width(q: int, dspec: DistSpec) -> None:
    if q > dspec.p_global:
        raise ValueError(f"batch width {q} > p_global {dspec.p_global}")


def apply(mesh: Mesh, dspec: DistSpec, dstate: DistState, ops: engine.OpBatch,
          ctx: engine.LinkCtx | None = None, *, donate: bool = False):
    """Linearize a mixed table batch across the mesh in ONE collective round.

    `ops` has up to p_global lanes laid out source-major (lane i issues from
    shard i // p_local; missing trailing lanes are IDLE-padded and their
    results trimmed away); `ctx` carries per-lane LL/SC links across
    batches.  `donate=True` hands the state's buffers to the round, which
    updates each shard in place instead of holding the old and the new
    state; the passed `dstate` must not be used afterwards.  Donation is
    skipped on CPU backends, which cannot donate.

    Returns (dstate', ctx', ApplyResult, overflow) where `overflow` is the
    per-lane bool mask of ops rejected by route capacity — reported, never
    silently dropped; rejected lanes have success=False and no table effect.
    """
    with TraceAnnotation(SPAN_APPLY):
        with TraceAnnotation(SPAN_VALIDATE):
            if dspec.is_hash:
                raise TypeError("hash DistSpec: use distributed.apply_hash")
            engine.check_kinds(ops.kind, engine.TABLE_KINDS, "table")
            q, k = ops.kind.shape[0], dspec.inner.k
            _check_width(q, dspec)
        telem = obs_telemetry.carry_in(dstate.local, ops.kind)
        with TraceAnnotation(SPAN_LAUNCH):
            ops = _pad_ops(ops, dspec.p_global)
            if ctx is not None:
                ctx = _pad_ctx(ctx, dspec.p_global, k)
            build = (_build_table_apply_2level if dspec.n_nodes > 1
                     else _build_table_apply)
            fn = build(mesh, dspec, engine_round.configured_mode(),
                       donate and jax.default_backend() != "cpu")
            local, nctx, value, success, overflow = fn(
                dstate.local, ctx, ops.kind, ops.slot, ops.expected,
                ops.desired)
        if q != dspec.p_global:
            nctx = engine.LinkCtx(*[x[:q] for x in nctx])
            value, success, overflow = value[:q], success[:q], overflow[:q]
        if telem is not None:
            # One tiny scalar-accumulate dispatch per collective round when
            # counters are on (threading the pytree through shard_map is
            # not worth the churn); zero work when off.
            # `collective_words(dspec)` is static per dspec, so the jitted
            # accumulator never retraces.
            obs_telemetry.record_dist(overflow, collective_words(dspec))
        return (DistState(local), nctx, engine.ApplyResult(value, success),
                overflow)


class DistRoundHandle:
    """An in-flight distributed round (the collective analog of
    `engine.RoundHandle`): `apply_round` returns immediately thanks to
    JAX async dispatch, so the executor routes/packs the NEXT stream's
    batch while this round's all_to_alls are still on the wire.  `order`
    (when requested) is the host-side claimed linearization — computed
    up front, so oracle replay never has to wait on the device."""

    __slots__ = ("state", "ctx", "result", "overflow", "order")

    def __init__(self, state, ctx, result, overflow, order=None):
        self.state = state
        self.ctx = ctx
        self.result = result
        self.overflow = overflow
        self.order = order

    def _leaves(self):
        return jax.tree_util.tree_leaves(
            (self.state, self.ctx, self.result, self.overflow))

    def ready(self) -> bool:
        return all(getattr(leaf, "is_ready", lambda: True)()
                   for leaf in self._leaves())

    def wait(self) -> "DistRoundHandle":
        jax.block_until_ready(self._leaves())
        return self


def apply_round(mesh: Mesh, dspec: DistSpec, dstate: DistState,
                ops: engine.OpBatch, ctx: engine.LinkCtx | None = None, *,
                with_order: bool = False) -> DistRoundHandle:
    """`apply` wrapped as an overlappable handle for the executor; with
    `with_order=True` the claimed linearization rides along for replay."""
    order = None
    if with_order:
        order, _ = linearization_order(dspec, ops)
    state, nctx, res, ovf = apply(mesh, dspec, dstate, ops, ctx)
    return DistRoundHandle(state, nctx, res, ovf, order)


# ---------------------------------------------------------------------------
# Cross-shard MCAS: the two-round prepare/commit collective (DESIGN.md §7).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _build_mcas_apply(mesh: Mesh, dspec: DistSpec, t_local: int, w: int):
    """One prepare/commit round pair for up to `t_local` transactions of
    width `w` per source device, as a single shard_mapped program:

      prepare — every active txn lane routes (cell, expected, desired,
                global txn id) to its owner shard; the owner LLs the cell
                through the local engine, checks expected, and VOTES: a
                lane's vote is yes iff it matched AND its txn id is the
                lowest matching id claiming that cell (the per-owner vote —
                arbitration needs no global view because ids are global).
                Match + vote + the witnessed value route back.
      decide  — the SOURCE holds all of its txn's lanes, so the commit
                mask is local: commit iff every lane matched and voted.
      commit  — the commit bit routes out over the SAME lane packing (so
                it lands on the owner's phase-A link ctx), the owner SCs
                every committing lane (one-round fast path: links predate
                the batch, voted lanes are cell-disjoint across txns), and
                SC success routes back.

    Nothing writes during prepare, so a transaction's reads — even spanning
    shards — form a consistent global snapshot; voted lanes are pairwise
    cell-disjoint, so commit is all-or-nothing by construction.
    """
    s, axis = dspec.n_shards, dspec.axis
    lsp: AtomicSpec = dspec.local_spec()
    k = lsp.k
    p_lane = t_local * w
    cap = p_lane                 # a source owns p_lane lanes: never overflows

    def local_fn(state, slot, expected, desired, active):
        st = _unstack(state)
        impl = registry.get_strategy(lsp.strategy)
        my = lax.axis_index(axis).astype(jnp.int32)
        gid_t = my * t_local + jnp.arange(t_local, dtype=jnp.int32)
        gid = jnp.repeat(gid_t, w)
        f_slot = slot.reshape(p_lane)
        f_exp = expected.reshape(p_lane, k)
        f_des = desired.reshape(p_lane, k)
        lane_used = (f_slot >= 0) & (f_slot < dspec.n_global)
        live = active.reshape(t_local)[jnp.arange(p_lane) // w] & lane_used

        owner, lslot = _owner_and_local(dspec, jnp.where(lane_used,
                                                         f_slot, 0))
        owner = jnp.where(live, owner, s)
        rank, fits = _dst_ranks(owner, cap, s, p_lane)

        # -- prepare: route (cell, expected, desired, gid) to the owner ----
        dst = jnp.where(fits, owner * cap + rank, s * cap)
        pack = _packer(dst, s * cap)
        go = _a2a(axis, s, cap)
        r_live = go(pack(fits, False)).reshape(s * cap)
        r_slot = go(pack(lslot, 0)).reshape(s * cap)
        r_exp = go(pack(f_exp, 0)).reshape(s * cap, k)
        r_des = go(pack(f_des, 0)).reshape(s * cap, k)
        r_gid = go(pack(gid, s * t_local)).reshape(s * cap)

        ops1 = engine.OpBatch(
            jnp.where(r_live, engine.LL, engine.IDLE), r_slot,
            jnp.zeros((s * cap, k), WORD_DTYPE),
            jnp.zeros((s * cap, k), WORD_DTYPE))
        d1, v1, octx, res1, st1 = engine.linearize(
            impl.engine_view(st), st.version,
            engine.init_ctx(s * cap, k), ops1)
        st = impl.commit(st, d1, v1, st1.n_updates, s * cap)
        vals = res1.value
        match = r_live & jnp.all(vals == r_exp, axis=1)
        # per-owner vote: lowest MATCHING txn id claiming each local cell
        n_loc = dspec.n_local
        claim = jnp.where(match, r_slot, n_loc)
        cgid = jnp.where(match, r_gid, s * t_local)
        cell_min = jnp.full((n_loc + 1,), s * t_local, jnp.int32)
        cell_min = cell_min.at[claim].min(cgid, mode="drop")
        vote = match & (cell_min[jnp.minimum(claim, n_loc)] == r_gid)

        # -- route match/vote/witness back to the source -------------------
        b_match = go(match).reshape(s, cap)
        b_vote = go(vote).reshape(s, cap)
        b_val = go(vals).reshape(s, cap, k)
        safe_owner = jnp.clip(owner, 0, s - 1)
        safe_pos = jnp.maximum(jnp.where(fits, rank, -1), 0)
        l_match = jnp.where(fits, b_match[safe_owner, safe_pos], False)
        l_vote = jnp.where(fits, b_vote[safe_owner, safe_pos], False)
        l_wit = jnp.where(fits[:, None], b_val[safe_owner, safe_pos], 0)

        def per_txn_all(flag):
            return jnp.all((flag | ~lane_used).reshape(t_local, w), axis=1)

        act_t = active.reshape(t_local)
        match_t = act_t & per_txn_all(l_match)
        commit_t = match_t & per_txn_all(l_vote)

        # -- commit: the commit bit rides the SAME packing onto the same
        #    owner lanes (phase-A links), then SC success rides back -------
        commit_lane = commit_t[jnp.arange(p_lane) // w] & lane_used
        r_commit = go(pack(commit_lane & fits, False)).reshape(s * cap)
        ops2 = engine.OpBatch(
            jnp.where(r_commit, engine.SC, engine.IDLE), r_slot,
            jnp.zeros((s * cap, k), WORD_DTYPE), r_des)
        d2, v2, _octx2, res2, st2 = engine.linearize(
            impl.engine_view(st), st.version, octx, ops2)
        st = impl.commit(st, d2, v2, st2.n_updates, s * cap)
        b_sc = go(res2.success).reshape(s, cap)
        l_sc = jnp.where(fits, b_sc[safe_owner, safe_pos], False)
        success_t = commit_t & per_txn_all(l_sc)
        return (_restack(st), match_t, success_t,
                l_wit.reshape(t_local, w, k))

    spec = P(axis)
    mapped = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec,) * 5,
                           out_specs=(spec,) * 4, check_vma=False)
    return jax.jit(mapped)


def mcas(mesh: Mesh, dspec: DistSpec, dstate: DistState, txns, *,
         policy=None, max_rounds: int | None = None):
    """Cross-shard k-word MCAS: transactions whose lanes span shards commit
    all-or-nothing through the two-round prepare/commit collective.

    `txns` is a `repro.txn.mcas.TxnBatch` of T transactions issued
    source-major (txn i from shard i // ceil(T / n_shards); T is IDLE-padded
    to a shard multiple).  Retries of arbitration losers run host-side under
    the queue's Dice-style `BackoffPolicy` (default none).  Each round moves
    `n_shards * t_local * w * (3k + 7)` words per device through four
    all_to_alls (`mcas_collective_words` is the exact model).

    Returns (dstate', McasResult) — same result contract, claimed
    linearization and `TxnOracle` compatibility as the single-device
    `repro.txn.mcas.mcas` (`txn.mcas.linearization_order(result)`).
    """
    from repro.sync.queue import BackoffPolicy
    from repro.txn import mcas as txn_mcas
    if dspec.is_hash:
        raise TypeError("hash DistSpec: MCAS runs on tables")
    if dspec.n_nodes > 1:
        raise NotImplementedError("cross-shard MCAS routes flat; build its "
                                  "DistSpec with n_nodes=1")
    policy = policy or BackoffPolicy("none")
    t, w, k = txns.t, txns.w, dspec.inner.k
    if txns.expected.shape[2] != k:
        raise ValueError(f"txn word width {txns.expected.shape[2]} != "
                         f"spec.k {k}")
    if max_rounds is None:
        max_rounds = txn_mcas.max_rounds_bound(t, policy)
    s = dspec.n_shards
    t_local = -(-t // s)
    t_pad = t_local * s
    pad = t_pad - t
    slot = jnp.concatenate(
        [jnp.asarray(txns.slot, jnp.int32),
         jnp.full((pad, w), -1, jnp.int32)]) if pad else \
        jnp.asarray(txns.slot, jnp.int32)
    expected = jnp.concatenate(
        [jnp.asarray(txns.expected, WORD_DTYPE),
         jnp.zeros((pad, w, k), WORD_DTYPE)]) if pad else \
        jnp.asarray(txns.expected, WORD_DTYPE)
    desired = jnp.concatenate(
        [jnp.asarray(txns.desired, WORD_DTYPE),
         jnp.zeros((pad, w, k), WORD_DTYPE)]) if pad else \
        jnp.asarray(txns.desired, WORD_DTYPE)
    fn = _build_mcas_apply(mesh, dspec, t_local, w)

    pending = np.concatenate([np.ones(t, bool), np.zeros(pad, bool)])
    success = np.zeros(t_pad, bool)
    witness = np.zeros((t_pad, w, k), np.uint32)
    round_res = np.zeros(t_pad, np.int32)
    attempts = np.zeros(t_pad, np.int32)
    delay = np.zeros(t_pad, np.int32)
    rnd = 0
    while pending.any():
        rnd += 1
        if rnd > max_rounds:
            raise RuntimeError(f"mcas round bound exceeded ({max_rounds}); "
                               f"pending={np.nonzero(pending)[0].tolist()}")
        active = pending & (delay <= 0)
        if not active.any():
            delay = np.maximum(delay - 1, 0)
            continue
        local, match_t, success_t, wit = fn(
            dstate.local, slot, expected, desired, jnp.asarray(active))
        dstate = DistState(local)
        match_t = np.asarray(match_t)
        success_t = np.asarray(success_t)
        failed = active & ~match_t
        committed = active & success_t
        resolved = failed | committed
        witness = np.where(resolved[:, None, None], np.asarray(wit), witness)
        success |= committed
        round_res = np.where(resolved, rnd, round_res)
        pending &= ~resolved
        lost = active & ~resolved
        attempts += lost.astype(np.int32)
        for i in np.nonzero(lost)[0]:
            delay[i] = policy.delay(int(attempts[i]))
        delay[~lost] = np.maximum(delay[~lost] - 1, 0)
    result = txn_mcas.McasResult(
        success[:t], jnp.asarray(witness[:t]), round_res[:t], attempts[:t],
        np.int32(rnd))
    return dstate, result


def mcas_collective_words(dspec: DistSpec, t_local: int, w: int) -> int:
    """Words per device per prepare/commit round pair (4 all_to_alls):
    out (slot, expected[k], desired[k], gid, live) + back (match, vote,
    witness[k]) + commit out/back (2)."""
    return dspec.n_shards * t_local * w * (3 * dspec.inner.k + 7)


# ---------------------------------------------------------------------------
# Sharded CacheHash: FIND/INSERT/DELETE route by key owner.
# ---------------------------------------------------------------------------

def _hash_owner(dspec: DistSpec, key_bits):
    """Owner shard of each key: top bits of the bucket hash (the local
    apply re-derives the local bucket from the SAME hash's low bits)."""
    hs: HashSpec = dspec.inner
    gb = (ch.hash_u32(key_bits.astype(jnp.uint32))
          & jnp.uint32(hs.nb - 1)).astype(jnp.int32)
    return gb // dspec.n_local


@functools.lru_cache(maxsize=256)
def _build_hash_apply(mesh: Mesh, dspec: DistSpec):
    s, cap, axis = dspec.n_shards, dspec.cap, dspec.axis
    lsp: HashSpec = dspec.local_spec()
    p_local, vw = dspec.p_local, lsp.vw

    def local_fn(state, kind, key, value):
        st = _unstack(state)
        active = kind != engine.IDLE
        owner = jnp.where(active, _hash_owner(dspec, key), s)
        rank, fits = _dst_ranks(owner, cap, s, p_local)

        dst = jnp.where(fits, owner * cap + rank, s * cap)
        pack = _packer(dst, s * cap)
        snd_kind = pack(jnp.where(fits, kind, engine.IDLE), engine.IDLE)
        snd_key = pack(key, 0)
        snd_val = pack(value, 0)
        go = _a2a(axis, s, cap)
        r_kind = go(snd_kind).reshape(s * cap)
        r_key = go(snd_key).reshape(s * cap)
        r_val = go(snd_val).reshape(s * cap, vw)

        rops = ch.make_hash_ops(r_kind, r_key.astype(jnp.uint32), r_val,
                                vw=vw)
        st, res, _stats = ch.apply_hash(lsp, st, rops)

        b_found = go(res.found).reshape(s, cap)
        b_val = go(res.value).reshape(s, cap, vw)
        b_over = go(res.overflow).reshape(s, cap)
        safe_owner = jnp.clip(owner, 0, s - 1)
        safe_pos = jnp.maximum(jnp.where(fits, rank, -1), 0)
        found = jnp.where(fits, b_found[safe_owner, safe_pos], False)
        val = jnp.where(fits[:, None], b_val[safe_owner, safe_pos], 0)
        walk_over = jnp.where(fits, b_over[safe_owner, safe_pos], False)
        overflow = active & ~fits
        return _restack(st), found, val, walk_over, overflow

    spec = P(axis)
    mapped = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec,) * 4,
                           out_specs=(spec,) * 5, check_vma=False)
    return jax.jit(mapped)


def apply_hash(mesh: Mesh, dspec: DistSpec, dstate: DistState,
               ops: engine.OpBatch):
    """Key-owner-routed sharded CacheHash batch (unified hash schema).

    Returns (dstate', HashResult, overflow) — same overflow contract as
    `apply`: capacity-rejected lanes are reported with found=False, never
    silently dropped, and never touch any shard's table.
    """
    if not dspec.is_hash:
        raise TypeError("table DistSpec: use distributed.apply")
    engine.check_kinds(ops.kind, engine.HASH_KINDS, "hash")
    q = ops.kind.shape[0]
    _check_width(q, dspec)
    ops = _pad_ops(ops, dspec.p_global)
    fn = _build_hash_apply(mesh, dspec)
    local, found, value, walk_over, overflow = fn(
        dstate.local, ops.kind, ops.slot, ops.desired)
    if q != dspec.p_global:
        found, value = found[:q], value[:q]
        walk_over, overflow = walk_over[:q], overflow[:q]
    return DistState(local), ch.HashResult(found, value, walk_over), overflow


# ---------------------------------------------------------------------------
# Read-back: shard by shard for tables of any size; whole for small ones.
# ---------------------------------------------------------------------------

_CELL_FIELDS = ("data", "version", "bptr", "mark", "lock")


def _rows(st, take):
    """A local table state whose cells are `take` of st's (applied to each
    per-cell field; node pool, ring and counters kept whole), so the
    strategy's `logical` gives those cells' values."""
    return st._replace(**{f: take(getattr(st, f)) for f in _CELL_FIELDS
                          if getattr(st, f).shape[0]})


def _check_table(dspec: DistSpec) -> None:
    if dspec.is_hash:
        raise TypeError("hash DistSpec: tables only")


@functools.lru_cache(maxsize=64)
def _build_gather(mesh: Mesh, dspec: DistSpec):
    impl = registry.get_strategy(dspec.inner.strategy)
    axes = (dspec.node_axis, dspec.axis) if dspec.n_nodes > 1 \
        else dspec.axis

    def local_fn(state, slots):
        owner, lslot = _owner_and_local(dspec, slots)
        mine = owner == _shard_index(dspec)
        sub = _rows(_unstack(state),
                    lambda x: x[jnp.where(mine, lslot, 0)])
        vals = jnp.where(mine[:, None], impl.logical(sub), 0)
        vers = jnp.where(mine, sub.version, 0)
        return lax.psum(vals, axes), lax.psum(vers, axes)

    mapped = jax.shard_map(local_fn, mesh=mesh, in_specs=(_pspec(dspec), P()),
                           out_specs=(P(), P()), check_vma=False)
    return jax.jit(mapped)


def gather(mesh: Mesh, dspec: DistSpec, dstate: DistState, slots):
    """Logical values word[q, k] and versions uint32[q] of the global
    `slots` (tables): each shard reads the slots it owns from its own
    cells, and the answers are assembled per lane on every device.  No
    device holds more than its shard and q answers."""
    _check_table(dspec)
    slots = jnp.asarray(slots, jnp.int32)
    return _build_gather(mesh, dspec)(dstate.local, slots)


def count_cells(mesh: Mesh, dspec: DistSpec, dstate: DistState, pred,
                *args, block: int = 1 << 22) -> int:
    """The number of cells of a table for which `pred(cells, values,
    versions, *args)` holds: cells int32[m] global ids, values word[m, k]
    logical values, versions uint32[m] -> bool[m].  Each shard folds it
    over its own cells in blocks of `block` (m = min(block, n_local)),
    so no device holds more than its shard and one block's temporaries;
    `args` ride along replicated."""
    _check_table(dspec)
    impl = registry.get_strategy(dspec.inner.strategy)
    m = min(block, dspec.n_local)
    if dspec.n_local % m:
        raise ValueError(f"block {m} does not divide n_local "
                         f"{dspec.n_local}")

    def local_fn(state, *args):
        st = _unstack(state)
        cells = _local_cells(dspec)

        def body(j, total):
            def take(x):
                return lax.dynamic_slice_in_dim(x, j * m, m)
            sub = _rows(st, take)
            hit = pred(take(cells), impl.logical(sub), sub.version, *args)
            return total + jnp.sum(hit.astype(jnp.int32))

        total = lax.fori_loop(0, dspec.n_local // m, body, jnp.int32(0))
        return total[None]

    spec = _pspec(dspec)
    mapped = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(spec,) + (P(),) * len(args),
                           out_specs=spec, check_vma=False)
    per_shard = jax.jit(mapped)(dstate.local, *args)
    return int(np.sum(np.asarray(per_shard, np.int64)))


def logical(dspec: DistSpec, dstate: DistState) -> jax.Array:
    """Global logical values [n, k], de-sharded (tables only), on one
    device: for small tables.  A large one reads back through `gather`
    and `count_cells`."""
    impl = registry.get_strategy(dspec.inner.strategy)
    vals = jax.vmap(impl.logical)(dstate.local)      # [s, n_local, k]
    if dspec.interleave:
        return jnp.swapaxes(vals, 0, 1).reshape(dspec.n_global, -1)
    return vals.reshape(dspec.n_global, -1)


def versions(dspec: DistSpec, dstate: DistState) -> jax.Array:
    """Global cell versions [n] (tables only), de-sharded on one device:
    for small tables, as `logical`."""
    ver = dstate.local.version                       # [s, n_local]
    if dspec.interleave:
        return jnp.swapaxes(ver, 0, 1).reshape(-1)
    return ver.reshape(-1)


def hash_items(dspec: DistSpec, dstate: DistState) -> dict:
    """All (key, value) pairs across every shard's CacheHash."""
    hs: HashSpec = dspec.inner
    out: dict = {}
    for i in range(dspec.n_shards):
        shard = jax.tree_util.tree_map(lambda x: np.asarray(x)[i],
                                       dstate.local)
        out.update(ch.items(shard, inline=hs.inline, vw=hs.vw))
    return out


def collective_words(dspec: DistSpec) -> int:
    """Exact words each device moves through the all_to_alls per batch
    (the roofline term the §Perf hillclimb drives down).  Hierarchical
    specs split into an intra-node term (phase 1 also carries the owner
    node id) and a cross-node term (phase 2 also rides the executed bit
    back) — the CROSS-NODE words drop from n_shards*cap to
    n_nodes*cap2 per device, which is the whole point."""
    if not dspec.is_hash and dspec.n_nodes > 1:
        k = dspec.inner.k
        return (dspec.devs_per_node * dspec.cap * (3 * k + 8)
                + dspec.n_nodes * dspec.cap2 * (3 * k + 7))
    per_lane = (2 * dspec.inner.vw + 4) if dspec.is_hash \
        else (3 * dspec.inner.k + 6)
    return dspec.n_shards * dspec.cap * per_lane


# ---------------------------------------------------------------------------
# The claimed linearization (host-side, for the oracle harness).
# ---------------------------------------------------------------------------

def _hash_u32_np(key):
    """Host-side bucket hash: evaluate THE jax implementation so device
    routing and the claimed order can never diverge."""
    return np.asarray(ch.hash_u32(jnp.asarray(key, jnp.uint32)))


def linearization_order(dspec: DistSpec, ops: engine.OpBatch):
    """The total order `apply`/`apply_hash` claims for a batch.

    Returns (order, overflow): `order` lists the executed lane ids in the
    claimed global sequence (owner-major, then source device, then in-bucket
    rank = lane order; dedup'd loads ride directly after their
    representative), `overflow` is the bool[p_global] mask of
    capacity-rejected lanes.  Feed both to `tests/oracle.py`.

    Hierarchical specs (n_nodes > 1) claim (owner, src node, src device,
    lane) with capacity charged at BOTH hops: cap per (src device, in-node
    owner index) — lanes bound for different nodes share a relay budget —
    then cap2 per (relay, owner node) in relay-lane arrival order.
    """
    kind = np.asarray(ops.kind)
    slot = np.asarray(ops.slot)
    p, s, pl, cap = dspec.p_global, dspec.n_shards, dspec.p_local, dspec.cap
    q = kind.shape[0]
    _check_width(q, dspec)
    if q < p:                                  # mirror apply's IDLE padding
        kind = np.concatenate([kind, np.full(p - q, engine.IDLE, np.int32)])
        slot = np.concatenate([slot, np.zeros(p - q, np.int32)])
    if dspec.is_hash:
        gb = (_hash_u32_np(slot) & np.uint32(dspec.inner.nb - 1)) \
            .astype(np.int64)
        owner_of = gb // dspec.n_local
    elif dspec.interleave:
        owner_of = slot % s
    else:
        owner_of = np.clip(slot // dspec.n_local, 0, s - 1)

    active = kind != engine.IDLE
    rep = np.arange(p)
    dups: dict[int, list[int]] = {}
    if dspec.dedup_loads and not dspec.is_hash:
        for src in range(s):
            groups: dict[int, list[int]] = {}
            for i in range(src * pl, (src + 1) * pl):
                if active[i]:
                    groups.setdefault(int(slot[i]), []).append(i)
            for lanes in groups.values():
                if all(kind[i] == engine.LOAD for i in lanes) \
                        and len(lanes) > 1:
                    first = lanes[0]
                    dups[first] = lanes[1:]
                    for i in lanes[1:]:
                        rep[i] = first

    overflow = np.zeros(p, bool)
    order: list[int] = []
    if not dspec.is_hash and dspec.n_nodes > 1:
        nn, d, cap2 = dspec.n_nodes, dspec.devs_per_node, dspec.cap2
        # phase 1: per source device, cap lanes per in-node owner index
        # (relay) — relay buffers fill src-device-major, lane order.
        relay: dict[tuple[int, int], list[int]] = {
            (m, j): [] for m in range(nn) for j in range(d)}
        for g in range(s):
            m = g // d
            cnt1: dict[int, int] = {}
            for i in range(g * pl, (g + 1) * pl):
                if not active[i] or rep[i] != i:
                    continue
                j = int(owner_of[i]) % d
                c = cnt1.get(j, 0)
                if c < cap:
                    relay[(m, j)].append(i)
                    cnt1[j] = c + 1
                else:
                    overflow[i] = True
                    for x in dups.get(i, []):
                        overflow[x] = True
        # phase 2: per relay, cap2 lanes per owner node, arrival order.
        accepted: dict[tuple[int, int], list[int]] = {}
        for (m, j), lanes in relay.items():
            cnt2: dict[int, int] = {}
            for i in lanes:
                onode = int(owner_of[i]) // d
                c = cnt2.get(onode, 0)
                if c < cap2:
                    accepted.setdefault((int(owner_of[i]), m), []).append(i)
                    cnt2[onode] = c + 1
                else:
                    overflow[i] = True
                    for x in dups.get(i, []):
                        overflow[x] = True
        for o in range(s):
            for m in range(nn):
                for i in accepted.get((o, m), []):
                    order.append(i)
                    order.extend(dups.get(i, []))
        return np.asarray(order, np.int64), overflow[:q]
    for o in range(s):
        for src in range(s):
            cnt = 0
            for i in range(src * pl, (src + 1) * pl):
                if not active[i] or rep[i] != i or owner_of[i] != o:
                    continue
                if cnt < cap:
                    order.append(i)
                    order.extend(dups.get(i, []))
                    cnt += 1
                else:
                    overflow[i] = True
                    for j in dups.get(i, []):
                        overflow[j] = True
    return np.asarray(order, np.int64), overflow[:q]


# ---------------------------------------------------------------------------
# DEPRECATED v1 surface: raw (data, version) PLAIN table, load/store/CAS.
# ---------------------------------------------------------------------------

class ShardedTable(NamedTuple):
    """DEPRECATED raw sharded table; new code holds a `DistSpec`+`DistState`."""

    data: jax.Array        # word[n, k], sharded over axis 0
    version: jax.Array     # uint32[n], sharded over axis 0


def init_sharded(mesh: Mesh, axis: str, n: int, k: int,
                 initial: np.ndarray | None = None) -> ShardedTable:
    """DEPRECATED shim: use `init_dist(mesh, DistSpec(AtomicSpec(...)))`."""
    warn_once("core.distributed.init_sharded",
              "distributed.init_dist(mesh, DistSpec(...))")
    n_shards = _mesh_shards(mesh, axis)
    assert n % n_shards == 0, (n, n_shards)
    data = jnp.zeros((n, k), WORD_DTYPE) if initial is None \
        else jnp.asarray(initial, WORD_DTYPE)
    ver = jnp.zeros((n,), jnp.uint32)
    return ShardedTable(
        jax.device_put(data, NamedSharding(mesh, P(axis, None))),
        jax.device_put(ver, NamedSharding(mesh, P(axis))))


def _plain_local(table: ShardedTable, s: int, n_local: int, k: int
                 ) -> TableState:
    """Stacked PLAIN-layout local states viewing a raw ShardedTable."""
    z = lambda dt, shape: jnp.zeros(shape, dt)
    return TableState(
        data=table.data.reshape(s, n_local, k),
        version=table.version.reshape(s, n_local),
        bptr=z(jnp.int32, (s, 0)), mark=z(bool, (s, 0)),
        lock=z(jnp.uint32, (s, 0)), pool=z(WORD_DTYPE, (s, 0, k)),
        free_ring=z(jnp.int32, (s, 0)),
        ring_head=z(jnp.uint32, (s,)), alloc_gen=z(jnp.uint32, (s,)))


def make_apply(mesh: Mesh, axis: str, n: int, k: int, p_local: int,
               *, route_capacity: int | None = None,
               dedup_loads: bool = False, interleave: bool = False):
    """DEPRECATED shim: use `distributed.apply(mesh, DistSpec(...), ...)`.

    Returned fn keeps the v1 contract: (table, ops) ->
    (table', result, overflow_count)."""
    warn_once("core.distributed.make_apply",
              "distributed.apply(mesh, DistSpec(...), state, ops)")
    s = _mesh_shards(mesh, axis)
    dspec = DistSpec(AtomicSpec(n, k, "plain"), axis, s, p_local,
                     route_capacity=route_capacity, dedup_loads=dedup_loads,
                     interleave=interleave)
    fn = _build_table_apply(mesh, dspec, engine_round.configured_mode())

    @jax.jit
    def apply_ops(table: ShardedTable, ops: engine.OpBatch):
        local = _plain_local(table, s, n // s, k)
        ctx = engine.init_ctx(dspec.p_global, k)
        local, _, value, success, overflow = fn(
            local, ctx, ops.kind, ops.slot, ops.expected, ops.desired)
        return (ShardedTable(local.data.reshape(n, k),
                             local.version.reshape(n)),
                engine.ApplyResult(value, success),
                jnp.sum(overflow.astype(jnp.int32)))

    return apply_ops


def reference_apply(data, version, ops: engine.OpBatch, *, n_shards: int,
                    p_local: int, interleave: bool = False):
    """DEPRECATED sequential oracle (v1 signature); new tests use
    `tests/oracle.py` + `linearization_order`."""
    from repro.core import semantics as sem
    dspec = DistSpec(AtomicSpec(data.shape[0], data.shape[1], "plain"),
                     "shard", n_shards, p_local, interleave=interleave)
    seq, overflow = linearization_order(dspec, ops)
    kind = np.asarray(ops.kind)
    reordered = engine.OpBatch(
        jnp.asarray(kind[seq]), jnp.asarray(np.asarray(ops.slot)[seq]),
        jnp.asarray(np.asarray(ops.expected)[seq]),
        jnp.asarray(np.asarray(ops.desired)[seq]))
    d2, v2, res = sem.apply_batch_reference(data, version, reordered)
    p = kind.shape[0]
    k = data.shape[1]
    value = np.zeros((p, k), data.dtype)
    success = np.zeros((p,), bool)
    value[seq] = np.asarray(res.value)
    success[seq] = np.asarray(res.success)
    return d2, v2, engine.ApplyResult(value, success), \
        np.nonzero(overflow)[0].tolist()
