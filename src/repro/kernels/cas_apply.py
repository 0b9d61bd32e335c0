"""Pallas TPU kernel: one combining round of batched store/CAS.

The deterministic linearization in `repro.core.semantics` serializes updates
to the same cell into rounds; *within* one round every live op targets a
distinct cell, so a round is an embarrassingly parallel
gather -> compare -> conditional write-back.  This kernel is that round,
executed as *lane tiles*: grid step b owns `block` ops (8 sublanes x the
lane-aligned k words = the native TPU (8, 128) register tile once ops.py
pads k).  The table stays HBM-resident; the tile's cell and metadata rows
are gathered with OVERLAPPED DMAs (all `block` copies started before any
wait, per-lane semaphores), the whole tile is evaluated in registers at
once, and rows are written back in place through input/output aliasing
(write-back is serialized per lane because dead lanes share the dummy
row) — `ceil(p / block)` grid steps instead of the historical p single-row
steps, with the gather phase an overlapped HBM stream.

Dead lanes (ops not live in this round) are pointed at a reserved dummy row
n by the host wrapper; they rewrite that row with its own contents (benign).
Write-back of the *unchanged* value on CAS failure keeps the dataflow static
— the moral equivalent of the paper's compare_exchange leaving memory
untouched, expressed as an idempotent store (TPU has no conditional DMA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY

STORE = 1
CAS = 2

BLOCK = 8


def _kernel(block: int):
    def kernel(slot_ref, kind_ref, exp_ref, des_ref, data_in, meta_in,
               out_data, out_meta, succ_ref, wit_ref, rows, mrows,
               sems, msems, wsem):
        b = pl.program_id(0)

        def _gathers(j):
            s = slot_ref[b * block + j]
            return (
                pltpu.make_async_copy(out_data.at[pl.ds(s, 1)],
                                      rows.at[pl.ds(j, 1)], sems.at[j]),
                pltpu.make_async_copy(out_meta.at[pl.ds(s, 1)],
                                      mrows.at[pl.ds(j, 1)], msems.at[j]),
            )

        # Phase 1 — overlapped gather: start ALL of the tile's row DMAs
        # before waiting on any (within a round live slots are distinct;
        # dead lanes share the dummy row, and concurrent reads are benign).
        def start(j, _):
            for cp in _gathers(j):
                cp.start()
            return 0

        def wait(j, _):
            for cp in _gathers(j):
                cp.wait()
            return 0

        lax.fori_loop(0, block, start, 0)
        lax.fori_loop(0, block, wait, 0)

        # Phase 2 — evaluate the whole tile in registers.
        cur = rows[...]                            # [block, k]
        kind = kind_ref[...][:, 0]
        live = jnp.logical_or(kind == STORE, kind == CAS)
        match = jnp.all(cur == exp_ref[...], axis=1)
        ok = jnp.logical_and(live, jnp.logical_or(kind == STORE, match))
        wit_ref[...] = cur
        succ_ref[...] = ok.astype(jnp.int32)[:, None]
        rows[...] = jnp.where(ok[:, None], des_ref[...], cur)
        meta = mrows[...]
        mrows[...] = meta.at[:, 0].add(jnp.uint32(2) *
                                       ok.astype(jnp.uint32))

        # Phase 3 — write-back, serialized per lane: dead lanes all rewrite
        # the shared dummy row, so their stores must not be in flight
        # together.
        def writeback(j, _):
            s = slot_ref[b * block + j]
            cp = pltpu.make_async_copy(
                rows.at[pl.ds(j, 1)], out_data.at[pl.ds(s, 1)], wsem)
            cp.start()
            cp.wait()
            cp = pltpu.make_async_copy(
                mrows.at[pl.ds(j, 1)], out_meta.at[pl.ds(s, 1)], wsem)
            cp.start()
            cp.wait()
            return 0

        lax.fori_loop(0, block, writeback, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def cas_apply_round(data: jax.Array, meta: jax.Array, slot: jax.Array,
                    kind: jax.Array, expected: jax.Array, desired: jax.Array,
                    *, block: int = BLOCK, interpret: bool = False):
    """One conflict-free round.  data: uint32[n+1, k] (row n = dummy);
    meta: uint32[n+1, 2]; slot: int32[p] (dead lanes -> n); kind: int32[p]
    or [p, 1]; expected/desired: uint32[p, k].

    Returns (data', meta', success int32[p,1], witness uint32[p,k]).
    Within a round all live slots are distinct -> no write conflicts."""
    n1, k = data.shape
    p = slot.shape[0]
    kind = kind.reshape(p).astype(jnp.int32)
    pad = (-p) % block
    if pad:
        # Padding lanes are dead: they benignly rewrite the dummy row n.
        slot = jnp.concatenate([slot, jnp.full((pad,), n1 - 1, jnp.int32)])
        kind = jnp.concatenate([kind, jnp.zeros((pad,), jnp.int32)])
        expected = jnp.concatenate(
            [expected, jnp.zeros((pad, k), expected.dtype)])
        desired = jnp.concatenate(
            [desired, jnp.zeros((pad, k), desired.dtype)])
    pp = p + pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pp // block,),
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),    # kind tile
            pl.BlockSpec((block, k), lambda i, s: (i, 0)),    # expected tile
            pl.BlockSpec((block, k), lambda i, s: (i, 0)),    # desired tile
            pl.BlockSpec(memory_space=_ANY),                  # data (HBM)
            pl.BlockSpec(memory_space=_ANY),                  # meta (HBM)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=_ANY),                  # data back
            pl.BlockSpec(memory_space=_ANY),                  # meta back
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),    # success tile
            pl.BlockSpec((block, k), lambda i, s: (i, 0)),    # witness tile
        ],
        scratch_shapes=[
            pltpu.VMEM((block, k), data.dtype),
            pltpu.VMEM((block, 2), jnp.uint32),
            pltpu.SemaphoreType.DMA((block,)),
            pltpu.SemaphoreType.DMA((block,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    new_data, new_meta, succ, wit = pl.pallas_call(
        _kernel(block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n1, k), data.dtype),
            jax.ShapeDtypeStruct((n1, 2), meta.dtype),
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, k), data.dtype),
        ],
        # aliasing indices count ALL inputs incl. the scalar-prefetch operand
        # (slot=0) and the blocked op tiles, so data=4, meta=5
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(slot, kind.reshape(pp, 1), expected, desired, data, meta)
    return new_data, new_meta, succ[:p], wit[:p]
