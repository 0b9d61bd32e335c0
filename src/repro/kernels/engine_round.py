"""The fused engine round: blocked fast-path / slow-path megakernels.

`core/engine.linearize` gives every batch the full slow-path pipeline — two
stable argsorts, four segmented scans and a `lax.while_loop` of masked
gather -> check -> scatter rounds — even when the batch is collision-free.
The paper's whole performance story (Schweizer et al., "Evaluating the Cost
of Atomic Operations") is that the *uncontended* path must be one cache-line
round trip; this module is that path made real at the XLA/Pallas level:

  fast path   When a batch has no intra-batch slot collisions (or is
              read-only, where collisions cannot matter), every lane is
              independent: ONE blocked pass gathers each lane's cell row,
              evaluates LOAD/STORE/CAS/LL/SC/VALIDATE in registers, and
              scatters data+version back — no sort, no scans, no rounds.
              On TPU this is a Pallas kernel (grid over lane tiles of
              `block` lanes, per-lane scalars in SMEM, input/output
              aliasing, conditional write-back DMA); off-TPU it is the
              equivalent O(p) gather/compute/scatter XLA program.

  slow path   Contended batches sort by (slot, lane) once, then ONE Pallas
              pass replays the sorted lanes sequentially per cell segment:
              the window holding a cell is DMA'd into VMEM at its segment
              start, all its ops apply in registers, and the window is
              written back at the segment end — each dirty cell makes
              exactly one HBM round trip instead of L gather/scatter
              rounds.  Off-TPU the slow
              path is `engine.linearize` itself (the pure-XLA reference).

  dispatch    `fast_path_ok` is one cheap duplicate-scatter check; a
              `lax.cond` picks the branch at runtime.  The predicate is
              conservative: any batch it cannot prove independent takes the
              slow path, so a colliding batch can NEVER take the fast
              kernel (property-tested in tests/test_engine_round.py).

Strategies opt in through `StrategyImpl.lower_round` (DESIGN.md §8); the
round returned by `make_round` is signature-compatible with
`engine.linearize` and bit-identical to it on every in-contract batch
(slots of active lanes inside [0, n); out-of-range active slots are
formally out of contract — the kernels treat them as failed no-ops, where
`linearize` reports a clamp-gathered value).

The fast path subsumes `kernels/llsc_commit`: a pure-SC batch over distinct
cells is exactly a collision-free batch with SC lanes, so the one-round SC
commit is just the fast kernel with link versions routed in (stale links
arrive poisoned odd and can never match an even cell version).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import engine
from repro.core.engine import (
    ApplyResult, ApplyStats, CAS, IDLE, LL, LOAD, LinkCtx, OpBatch, SC,
    STORE, VALIDATE,
)

_ANY = pltpu.MemorySpace.ANY

# Lanes per grid step: the fast tier keeps this many window DMAs in flight.
DEFAULT_BLOCK = 8

_MODES = ("auto", "pallas", "xla", "off")


def configured_mode() -> str:
    """The engine-kernel mode requested by the environment.

    BIGATOMIC_ENGINE_KERNEL = auto (default) | pallas | xla | off:
      auto    pallas on TPU backends, xla elsewhere;
      pallas  always use the Pallas kernels (interpret=True off-TPU — the
              CI kernel-exercise mode);
      xla     fused round with the pure-XLA fast path (the CPU production
              mode: still skips sort+scans on collision-free batches);
      off     pure `engine.linearize` everywhere (the pre-kernel engine).
    """
    mode = os.environ.get("BIGATOMIC_ENGINE_KERNEL", "auto")
    if mode not in _MODES:
        raise ValueError(f"BIGATOMIC_ENGINE_KERNEL={mode!r}; "
                         f"expected one of {_MODES}")
    return mode


def resolved_mode(mode: str | None = None) -> tuple[str, bool]:
    """Resolve `auto` against the backend.  Returns (mode, interpret)."""
    mode = mode or configured_mode()
    on_tpu = jax.default_backend() == "tpu"
    if mode == "auto":
        mode = "pallas" if on_tpu else "xla"
    return mode, not on_tpu


# ---------------------------------------------------------------------------
# The fast-path predicate: one duplicate-scatter check.
# ---------------------------------------------------------------------------

def fast_path_ok(n: int, ops: OpBatch) -> jax.Array:
    """True iff every lane of the batch is provably independent.

    Exactly when (a) every active slot is in [0, n), AND (b) the batch is
    read-only (no STORE/CAS/SC — reads and validates commute freely even on
    the same cell) OR no two active lanes share a slot (one scatter-add of
    lane counts, then a max).  False positives are impossible by
    construction: a colliding batch with any write fails (b), so it can
    never take the fast kernel."""
    kind, slot = ops.kind, ops.slot
    active = kind != IDLE
    in_range = (slot >= 0) & (slot < n)
    all_in = ~jnp.any(active & ~in_range)
    is_write = active & ((kind == STORE) | (kind == CAS) | (kind == SC))
    read_only = ~jnp.any(is_write)
    cslot = jnp.where(active & in_range, slot, n)
    counts = jnp.zeros((n + 1,), jnp.int32).at[cslot].add(1, mode="drop")
    no_dup = jnp.max(counts[:n], initial=0) <= 1
    return all_in & (read_only | no_dup)


def path_counts(n: int, ops: OpBatch, *, fused: bool):
    """(eligible, taken) for the telemetry tier (`repro.obs`).

    `eligible` is the fast-path predicate above; `taken` is the branch the
    `lax.cond` in `make_round` resolves this batch to — identical to the
    predicate when the fused round is in play, statically False otherwise
    (engine-kernel mode `off`, or a strategy with no lowered round, routes
    every batch through the slow-path `linearize`)."""
    eligible = fast_path_ok(n, ops)
    taken = eligible if fused else jnp.zeros((), bool)
    return eligible, taken


# ---------------------------------------------------------------------------
# Shared fast-path assembly: kernel/XLA producers feed the same epilogue.
# ---------------------------------------------------------------------------

def _poisoned_link_ver(ctx: LinkCtx, slot: jax.Array) -> jax.Array:
    """A lane's link version, odd-poisoned when the link cannot validate
    (dead link or link naming a different cell) — cell versions are always
    even, so a poisoned link never matches (the llsc_commit idiom)."""
    link_ok = ctx.linked & (ctx.slot == slot)
    return jnp.where(link_ok, ctx.version, jnp.uint32(1))


def _assemble_fast(n: int, ctx: LinkCtx, ops: OpBatch, link_ver, cur, ver,
                   okw, new_data, new_version):
    """Per-lane results / ctx / stats for an independent (fast-path) batch.

    cur/ver are each lane's pre-batch cell value+version; okw is write
    success for STORE/CAS/SC lanes (False elsewhere)."""
    kind = ops.kind
    active = kind != IDLE
    is_read = (kind == LOAD) | (kind == LL)
    is_valcas = active & ((kind == STORE) | (kind == CAS))
    is_sc = active & (kind == SC)
    is_upd = is_valcas | is_sc

    vl_ok = link_ver == ver                      # poisoned-odd never matches
    success = jnp.where(
        is_read | (kind == STORE), active,
        jnp.where(kind == VALIDATE, vl_ok,
                  jnp.where(is_upd, okw, False)))
    value = jnp.where(active[:, None], cur, jnp.zeros_like(cur))

    is_ll = (kind == LL) & active
    new_ctx = LinkCtx(
        slot=jnp.where(is_ll, ops.slot, ctx.slot),
        version=jnp.where(is_ll, ver, ctx.version),
        value=jnp.where(is_ll[:, None], cur, ctx.value),
        linked=jnp.where(is_ll, True,
                         jnp.where(kind == SC, False, ctx.linked)),
    )
    stats = ApplyStats(
        rounds=jnp.any(is_upd).astype(jnp.int32),
        n_updates=jnp.sum((is_valcas | (is_sc & okw)).astype(jnp.int32)),
        n_loads=jnp.sum((active & is_read).astype(jnp.int32)),
        n_cas_fail=jnp.sum((((kind == CAS) & active) | is_sc) & ~okw)
        .astype(jnp.int32),
        # No two lanes share a written cell on the fast path, so no load
        # ever races a write and every successful write dirties its own cell.
        n_raced_loads=jnp.int32(0),
        n_dirty_cells=jnp.sum(okw.astype(jnp.int32)),
    )
    return new_data, new_version, new_ctx, ApplyResult(value, success), stats


def _fast_xla(n: int, data, version, ctx: LinkCtx, ops: OpBatch):
    """Pure-XLA fast path: one gather, register math, one scatter.  No sort,
    no scans, no rounds — the off-TPU production fast path."""
    kind, slot = ops.kind, ops.slot
    active = kind != IDLE
    safe = jnp.clip(slot, 0, n - 1)
    cur = data[safe]
    ver = version[safe]
    match = jnp.all(cur == ops.expected, axis=1)
    link_ver = _poisoned_link_ver(ctx, slot)
    okw = active & ((kind == STORE) | ((kind == CAS) & match)
                    | ((kind == SC) & (link_ver == ver)))
    w_idx = jnp.where(okw, slot, n)
    new_data = data.at[w_idx].set(ops.desired, mode="drop")
    new_version = version.at[w_idx].add(jnp.uint32(2), mode="drop")
    return _assemble_fast(n, ctx, ops, link_ver, cur, ver, okw,
                          new_data, new_version)


# ---------------------------------------------------------------------------
# The table as the kernels see it: the state's own buffers, re-viewed.
# ---------------------------------------------------------------------------
#
# A Pallas kernel takes an HBM operand in its plain row-major (8, 128)-tiled
# layout; XLA re-lays any other operand into a copy.  The device keeps
# data[n, k] with k < 128 column-major, in (k, 128) tiles of 128 cells, and
# handed over as [n, k] it would be copied and padded to 128 lanes -- 32x for
# k=4, 128x for version[n, 1] -- which at a deployment's table size does not
# fit the chip.  So the kernels see the same bytes under shapes whose
# row-major layout they already have:
#
#   data     k < 128   data.T: [k, n]         cell s = column s
#            k >= 128  [n * k / 128, 128]     cell s = rows [s*k/128, ...)
#   version            [n / 128, 128]         cell s = row s // 128, lane s % 128
#
# Both are bitcasts when 128 divides n and k fits Mosaic's row tiling (k of
# 1, 2, 4 or a multiple of 8 below 128; a multiple of 128 above).  Other
# widths are padded to the next such width, a copy of the table.  A lane
# moves the window of whole 128-lane rows that holds its cell -- k columns
# or k/128 rows, the units Mosaic can DMA at any cell -- and picks its own
# words out of it with a lane mask.

LANES = 128

# Per-lane scalars: one SMEM row of _NMETA int32 words per lane.
_NMETA = 8
_DPOS, _VROW, _LANE, _KIND, _LINK, _FLAGS = range(6)
_LIVE, _SEG_START, _SEG_END = 1, 2, 4


def _columns(k: int) -> bool:
    """True where the data view is data.T (one cell per lane)."""
    return k < LANES


def _view_words(k: int) -> int:
    """Words per cell in the view: k padded to Mosaic's row tiling."""
    if not _columns(k):
        return -(-k // LANES) * LANES
    if k in (1, 2, 4):
        return k
    return 4 if k == 3 else -(-k // 8) * 8


def _window_rows(k: int) -> int:
    """Rows of the data window a lane moves: k columns, or k/128 rows."""
    kw = _view_words(k)
    return kw if _columns(k) else kw // LANES


def _table_view(data, version):
    n, k = data.shape
    pad, kw = (-n) % LANES, _view_words(k)
    if pad or kw != k:
        data = jnp.pad(data, ((0, pad), (0, kw - k)))
        version = jnp.pad(version, (0, pad))
    vrows = version.reshape(-1, LANES)
    if _columns(k):
        return data.T, vrows
    return data.reshape(-1, LANES), vrows


def _table_unview(drows, vrows, n: int, k: int):
    version = vrows.reshape(-1)[:n]
    if _columns(k):
        return drows.T[:n, :k], version
    return drows.reshape(-1, _view_words(k))[:n, :k], version


def _lane_rows(words):
    """word[p, k] -> word[p * window_rows, 128]: each lane's operand laid out
    like its data window, so a lane-mask select moves it into place."""
    p, k = words.shape
    kw = _view_words(k)
    if kw != k:
        words = jnp.pad(words, ((0, 0), (0, kw - k)))
    if _columns(k):
        return jnp.broadcast_to(words[:, :, None], (p, kw, LANES)).reshape(
            p * kw, LANES)
    return words.reshape(-1, LANES)


def _pad_lanes(block: int, n: int, slot, kind, link_ver, expected, desired):
    """Pad the batch to a whole number of lane tiles with dead lanes."""
    pad = (-slot.shape[0]) % block
    if not pad:
        return slot, kind, link_ver, expected, desired
    k = expected.shape[1]
    return (jnp.concatenate([slot, jnp.full((pad,), n, jnp.int32)]),
            jnp.concatenate([kind, jnp.full((pad,), IDLE, jnp.int32)]),
            jnp.concatenate([link_ver, jnp.ones((pad,), jnp.uint32)]),
            jnp.concatenate([expected, jnp.zeros((pad, k), expected.dtype)]),
            jnp.concatenate([desired, jnp.zeros((pad, k), desired.dtype)]))


def _lane_meta(n: int, k: int, slot, kind, link_ver, flags):
    """int32[p, _NMETA]: each lane's window position, version row, lane,
    kind, link version and flags.  Dead and out-of-contract lanes (slot
    outside [0, n)) point at cell 0 and are flagged not live, so no DMA
    index ever leaves the table."""
    live = (slot >= 0) & (slot < n)
    s = jnp.where(live, slot, 0)
    lane = s % LANES
    pos = s - lane if _columns(k) else s * _window_rows(k)
    zero = jnp.zeros_like(s)
    return jnp.stack([
        pos, s // LANES, lane, kind,
        lax.bitcast_convert_type(link_ver.astype(jnp.uint32), jnp.int32),
        flags | jnp.where(live, _LIVE, 0), zero, zero], axis=1)


def _lane_results(drows, vrows, wit, info, meta, n: int, k: int, p: int):
    """Undo the views: (data', version', witness[p, k], ver_pt[p], flag[p]),
    with flag the kernel's per-lane write or success bit."""
    data, version = _table_unview(drows, vrows, n, k)
    if _columns(k):
        wit = wit.reshape(-1, _view_words(k), LANES)[:p]
        wit = jnp.take_along_axis(wit, meta[:p, _LANE, None, None], axis=2)
        wit = wit[:, :k, 0]
    else:
        wit = wit.reshape(-1, _view_words(k))[:p, :k]
    return data, version, wit, info[:p, 0], info[:p, 1].astype(jnp.int32)


# ---------------------------------------------------------------------------
# One op against its cell, on windows in VMEM (shared by both kernels).
# ---------------------------------------------------------------------------

def _window(data_ref, pos, k: int):
    """The HBM window of whole 128-lane rows that holds a lane's cell."""
    if _columns(k):
        return data_ref.at[:, pl.ds(pl.multiple_of(pos, LANES), LANES)]
    return data_ref.at[pl.ds(pos, _window_rows(k))]


def _masks(k: int, lane):
    """(data mask, version mask): the cell's words in its data window and
    its word in its version row."""
    dlane = lax.broadcasted_iota(jnp.int32, (_window_rows(k), LANES), 1)
    vlane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    dmask = dlane == lane if _columns(k) else dlane >= 0
    return dmask, vlane == lane


def _judge(cv, vv, exp, kd, lv, dmask, vmask):
    """Evaluate one op.  cv/vv: the cell's data window and version row;
    exp: the lane's comparand rows; kd, lv: its kind and link version
    (scalars).  Returns (okw, succ, ver) as [1, 1] vectors: write success,
    op success (int32) and the cell version the op saw (uint32)."""
    eq = jnp.where(dmask, (cv == exp).astype(jnp.int32), 1)
    match = jnp.min(jnp.min(eq, axis=1, keepdims=True), axis=0, keepdims=True)
    # Mosaic reduces signed words only; the sum picks one word, so the
    # bitcasts round-trip it exactly.
    ver = lax.bitcast_convert_type(jnp.sum(
        lax.bitcast_convert_type(jnp.where(vmask, vv, jnp.uint32(0)),
                                 jnp.int32), axis=1, keepdims=True),
        jnp.uint32)
    link_ok = (ver == lv.astype(jnp.uint32)).astype(jnp.int32)

    def is_(c):
        return (kd == c).astype(jnp.int32)

    okw = is_(STORE) + is_(CAS) * match + is_(SC) * link_ok
    succ = (is_(LOAD) + is_(STORE) + is_(LL) + is_(CAS) * match
            + (is_(SC) + is_(VALIDATE)) * link_ok)
    return okw, succ, ver


def _commit(cv, vv, des, okw, dmask, vmask):
    """Window and version row after a write that succeeded iff okw."""
    return (jnp.where(jnp.where(dmask, okw, 0) > 0, des, cv),
            jnp.where(jnp.where(vmask, okw, 0) > 0, vv + jnp.uint32(2), vv))


def _info(ver, flag):
    """A lane's uint32[1, 128] result row: lane 0 the version at its point,
    lane 1 its write/success bit."""
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.where(lane == 0, ver,
                     jnp.where(lane == 1, flag.astype(jnp.uint32),
                               jnp.uint32(0)))


def _copy(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def _round_call(kernel, k: int, block: int, meta, exp_rows, des_rows, drows,
                vrows, scratch, interpret: bool):
    pp, wr = meta.shape[0], _window_rows(k)
    tile = pl.BlockSpec((block * wr, LANES), lambda i: (i, 0))
    table = pl.BlockSpec(memory_space=_ANY)
    return pl.pallas_call(
        kernel,
        grid=(pp // block,),
        in_specs=[
            pl.BlockSpec((block, _NMETA), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),              # lane scalars
            tile,                                               # expected
            tile,                                               # desired
            table,                                              # data
            table,                                              # version
        ],
        out_specs=[
            table,                                              # data back
            table,                                              # version back
            tile,                                               # witness
            pl.BlockSpec((block, LANES), lambda i: (i, 0)),     # lane info
        ],
        out_shape=[
            jax.ShapeDtypeStruct(drows.shape, drows.dtype),
            jax.ShapeDtypeStruct(vrows.shape, vrows.dtype),
            jax.ShapeDtypeStruct((pp * wr, LANES), drows.dtype),
            jax.ShapeDtypeStruct((pp, LANES), jnp.uint32),
        ],
        scratch_shapes=scratch,
        # the table is updated in place: data = input 3, version = input 4
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(meta, exp_rows, des_rows, drows, vrows)


# ---------------------------------------------------------------------------
# The blocked fast-path Pallas kernel.
# ---------------------------------------------------------------------------

def _fast_kernel(k: int, block: int):
    wr = _window_rows(k)

    def kernel(meta_ref, exp_ref, des_ref, data_in, ver_in, out_data,
               out_ver, wit_ref, info_ref, wins, vrows, win, vrow, sems,
               vsems, sem):
        del data_in, ver_in                 # aliased to out_data / out_ver

        def m(j, f):
            return meta_ref[j, f]

        def gathers(j):
            return (
                pltpu.make_async_copy(_window(out_data, m(j, _DPOS), k),
                                      wins.at[pl.ds(j * wr, wr)], sems.at[j]),
                pltpu.make_async_copy(out_ver.at[pl.ds(m(j, _VROW), 1)],
                                      vrows.at[pl.ds(j, 1)], vsems.at[j]),
            )

        # Phase 1 -- overlapped gather: every lane's window in flight at
        # once (dead lanes read cell 0's and ignore it).
        for j in range(block):
            for cp in gathers(j):
                cp.start()
        for j in range(block):
            for cp in gathers(j):
                cp.wait()

        for j in range(block):
            kd, live = m(j, _KIND), (m(j, _FLAGS) & _LIVE) != 0
            r = slice(j * wr, (j + 1) * wr)
            dmask, vmask = _masks(k, m(j, _LANE))
            cv = wins[r, :]
            okw, _, ver = _judge(cv, vrows[j:j + 1, :], exp_ref[r, :], kd,
                                 m(j, _LINK), dmask, vmask)
            on = live.astype(jnp.int32)
            wit_ref[r, :] = cv * on.astype(cv.dtype)
            info_ref[j:j + 1, :] = _info(ver * on.astype(ver.dtype), okw * on)

            # Phase 2 -- each writing lane's write-back is a read-modify-
            # write of its window: lanes hold distinct cells, but up to 128
            # of them share a window, so each merges its own words into the
            # window as it stands now.  The predicate is the lane's kind
            # (SMEM); a CAS that failed writes its window back unchanged.
            @pl.when(live & ((kd == STORE) | (kd == CAS) | (kd == SC)))
            def _():
                dwin = _window(out_data, m(j, _DPOS), k)
                vwin = out_ver.at[pl.ds(m(j, _VROW), 1)]
                _copy(dwin, win, sem)
                _copy(vwin, vrow, sem)
                win[...], vrow[...] = _commit(win[...], vrow[...],
                                              des_ref[r, :], okw, dmask,
                                              vmask)
                _copy(win, dwin, sem)
                _copy(vrow, vwin, sem)

    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fast_round_pallas(data, version, slot, kind, link_ver, expected, desired,
                      *, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """One blocked fast-path pass.  data: word[n, k]; version: uint32[n];
    slot: int32[p] (inactive lanes -> n); link_ver: uint32[p] (odd-poisoned
    when the lane's link cannot validate).  Precondition: active lanes
    target distinct in-range slots (or the batch is read-only).

    Returns (data', version', witness[p, k], ver_pt[p], okw[p])."""
    n, k = data.shape
    p, wr = slot.shape[0], _window_rows(k)
    slot, kind, link_ver, expected, desired = _pad_lanes(
        block, n, slot, kind, link_ver, expected, desired)
    meta = _lane_meta(n, k, slot, kind, link_ver, 0)
    drows, vrows = _table_view(data, version)
    scratch = [
        pltpu.VMEM((block * wr, LANES), data.dtype),
        pltpu.VMEM((block, LANES), jnp.uint32),
        pltpu.VMEM((wr, LANES), data.dtype),
        pltpu.VMEM((1, LANES), jnp.uint32),
        pltpu.SemaphoreType.DMA((block,)),
        pltpu.SemaphoreType.DMA((block,)),
        pltpu.SemaphoreType.DMA(()),
    ]
    out = _round_call(_fast_kernel(k, block), k, block, meta,
                      _lane_rows(expected), _lane_rows(desired), drows, vrows,
                      scratch, interpret)
    return _lane_results(*out, meta, n, k, p)


def _fast_pallas(n: int, data, version, ctx: LinkCtx, ops: OpBatch, *,
                 block: int, interpret: bool):
    slot = jnp.where(ops.kind != IDLE, ops.slot, n)
    link_ver = _poisoned_link_ver(ctx, ops.slot)
    new_data, new_version, wit, verpt, okw = fast_round_pallas(
        data, version, slot, ops.kind, link_ver, ops.expected, ops.desired,
        block=block, interpret=interpret)
    return _assemble_fast(n, ctx, ops, link_ver, wit, verpt, okw != 0,
                          new_data, new_version)


# ---------------------------------------------------------------------------
# The slow-path Pallas kernel: one sequential replay pass over sorted lanes.
# ---------------------------------------------------------------------------

def _slow_kernel(k: int, block: int):
    wr = _window_rows(k)

    def kernel(meta_ref, exp_ref, des_ref, data_in, ver_in, out_data,
               out_ver, wit_ref, info_ref, win, vrow, sem):
        del data_in, ver_in                 # aliased to out_data / out_ver

        def lane(j, _):
            def m(f):
                return meta_ref[j, f]

            flags = m(_FLAGS)
            dwin = _window(out_data, m(_DPOS), k)
            vwin = out_ver.at[pl.ds(m(_VROW), 1)]
            r = pl.ds(j * wr, wr)

            @pl.when((flags & _LIVE) != 0)
            def _():
                # Segment start: the cell's window makes its ONE trip in.
                @pl.when((flags & _SEG_START) != 0)
                def _():
                    _copy(dwin, win, sem)
                    _copy(vwin, vrow, sem)

                dmask, vmask = _masks(k, m(_LANE))
                cv, vv = win[...], vrow[...]
                okw, succ, ver = _judge(cv, vv, exp_ref[r, :], m(_KIND),
                                        m(_LINK), dmask, vmask)
                wit_ref[r, :] = cv
                info_ref[pl.ds(j, 1), :] = _info(ver, succ)
                win[...], vrow[...] = _commit(cv, vv, des_ref[r, :], okw,
                                              dmask, vmask)

                # Segment end: write the (possibly dirty) window back.  A
                # later segment on a cell of the same window reads it again.
                @pl.when((flags & _SEG_END) != 0)
                def _():
                    _copy(win, dwin, sem)
                    _copy(vrow, vwin, sem)

            @pl.when((flags & _LIVE) == 0)
            def _():
                wit_ref[r, :] = jnp.zeros((wr, LANES), wit_ref.dtype)
                info_ref[pl.ds(j, 1), :] = jnp.zeros((1, LANES), jnp.uint32)

            return 0

        lax.fori_loop(0, block, lane, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def slow_round_pallas(data, version, s_slot, s_kind, s_link_ver, s_expected,
                      s_desired, *, block: int = DEFAULT_BLOCK,
                      interpret: bool = False):
    """One fused sequential-replay pass over lanes SORTED by (slot, lane).

    Fuses the per-segment arbitration and all L combining rounds of
    `engine.linearize._general` into one kernel: a segment's cell window is
    DMA'd in once, every op of the segment applies in registers (full
    LOAD/STORE/CAS/LL/SC/VALIDATE semantics), and the window is written back
    once -- replacing the gather -> check -> scatter `while_loop` round
    trips.  The per-lane DMAs here are deliberately serialized: the replay
    is sequential by definition (lane j+1 may read what lane j wrote), so
    only the blocked op tiles pipeline across grid steps.

    Returns (data', version', val_pt[p, k], ver_pt[p], success[p]) in the
    SORTED lane order."""
    n, k = data.shape
    p, wr = s_slot.shape[0], _window_rows(k)
    # Padding lanes are dead (slot n) and sort AFTER every live lane, so
    # they never split a real segment.
    s_slot, s_kind, s_link_ver, s_expected, s_desired = _pad_lanes(
        block, n, s_slot, s_kind, s_link_ver, s_expected, s_desired)
    new_cell = s_slot[1:] != s_slot[:-1]
    edge = jnp.ones((1,), bool)
    flags = (jnp.where(jnp.concatenate([edge, new_cell]), _SEG_START, 0)
             | jnp.where(jnp.concatenate([new_cell, edge]), _SEG_END, 0))
    meta = _lane_meta(n, k, s_slot, s_kind, s_link_ver, flags)
    drows, vrows = _table_view(data, version)
    scratch = [
        pltpu.VMEM((wr, LANES), data.dtype),
        pltpu.VMEM((1, LANES), jnp.uint32),
        pltpu.SemaphoreType.DMA(()),
    ]
    out = _round_call(_slow_kernel(k, block), k, block, meta,
                      _lane_rows(s_expected), _lane_rows(s_desired), drows,
                      vrows, scratch, interpret)
    return _lane_results(*out, meta, n, k, p)


def _slow_pallas(n: int, data, version, ctx: LinkCtx, ops: OpBatch, *,
                 block: int, interpret: bool):
    """Sort once, replay in one kernel pass, then rebuild ctx/result/stats
    exactly as `linearize` defines them (two cheap scans; no while_loop)."""
    p, k = ops.desired.shape
    kind = ops.kind
    active = kind != IDLE
    slot = jnp.where(active, ops.slot, n)
    order = jnp.argsort(slot, stable=True)
    inv = jnp.argsort(order, stable=True)

    s_slot = slot[order]
    s_kind = kind[order]
    s_link_ver = _poisoned_link_ver(ctx, ops.slot)[order]

    new_data, new_version, val_s, verpt_s, succ_i = slow_round_pallas(
        data, version, s_slot, s_kind, s_link_ver, ops.expected[order],
        ops.desired[order], block=block, interpret=interpret)
    s_success = succ_i != 0

    is_ll = (s_kind == LL) & (s_slot < n)
    n_slot = jnp.where(is_ll, s_slot, ctx.slot[order])
    n_ver = jnp.where(is_ll, verpt_s, ctx.version[order])
    n_val = jnp.where(is_ll[:, None], val_s, ctx.value[order])
    n_lnk = jnp.where(is_ll, True,
                      jnp.where(s_kind == SC, False, ctx.linked[order]))
    new_ctx = LinkCtx(n_slot[inv], n_ver[inv], n_val[inv], n_lnk[inv])
    s_value = jnp.where((s_kind != IDLE)[:, None], val_s,
                        jnp.zeros_like(val_s))
    result = ApplyResult(s_value[inv], s_success[inv])

    # Stats: the single sorted-order definition shared with `linearize`.
    stats = engine.stats_on_sorted(n, s_slot, s_kind, s_success)
    return new_data, new_version, new_ctx, result, stats


# ---------------------------------------------------------------------------
# The round factory: what StrategyImpl.lower_round hands the engine.
# ---------------------------------------------------------------------------

def make_round(n: int, k: int, *, mode: str | None = None,
               interpret: bool | None = None, block: int = DEFAULT_BLOCK):
    """Build a fused round callable, signature-compatible with
    `engine.linearize`: (data, version, ctx, ops) ->
    (data', version', ctx', ApplyResult, ApplyStats).

    mode  'xla'    runtime fast path in pure XLA, `linearize` slow path;
          'pallas' blocked Pallas fast + slow kernels (interpret off-TPU);
          'off'/None resolves via `resolved_mode()`.
    """
    r_mode, r_interp = resolved_mode(mode)
    if interpret is None:
        interpret = r_interp
    if r_mode == "off":
        return engine.linearize

    def round_fn(data, version, ctx: LinkCtx, ops: OpBatch):
        # linearize gathers ctx lanes by sorted lane index, which for a ctx
        # wider than the batch means "the first p lanes"; replicate that so
        # both tiers see (and return) batch-width ctx exactly as it does.
        if ctx.slot.shape[0] != ops.p:
            ctx = LinkCtx(ctx.slot[:ops.p], ctx.version[:ops.p],
                          ctx.value[:ops.p], ctx.linked[:ops.p])
        take_fast = fast_path_ok(n, ops)
        if r_mode == "pallas":
            fast = functools.partial(_fast_pallas, n, block=block,
                                     interpret=interpret)
            slow = functools.partial(_slow_pallas, n, block=block,
                                     interpret=interpret)
        else:
            fast = functools.partial(_fast_xla, n)

            def slow(data, version, ctx, ops):
                return engine.linearize(data, version, ctx, ops)

        return lax.cond(take_fast, fast, slow, data, version, ctx, ops)

    return round_fn
