"""Pallas TPU kernel: version-validated k-word cell gather (the fast path).

This is the paper's whole point made into silicon-shaped code: a big-atomic
load is ONE contiguous cell read (data row + 2 metadata words) — no pointer
chase.  On TPU the k-word cell lives in HBM as a row of a [n, k] array; the
query indices arrive scalar-prefetched in SMEM, and each grid step owns a
*tile of `block` lanes* (8 sublanes x the lane-aligned k, the native (8, 128)
register tile once ops.py pads k): the kernel starts ALL of the tile's row
DMAs from the HBM-resident table before waiting on any (a per-lane
semaphore array keeps `block` copies in flight), so the gather is an
overlapped HBM stream at `ceil(q / block)` grid steps instead of the
historical one-lane-per-step shape with one dependent round trip per lane.

Layout notes (TPU adaptation):
  * cells are rows; k is padded by ops.py to a multiple of the 128-lane
    register width so each row DMA is lane-aligned;
  * the two metadata words (version, invalid-mark) are a [n, 2] array — on
    real silicon they share the cell's first cache line; here they ride a
    per-lane scratch DMA;
  * validation (version even && mark clear) is elementwise in VMEM; the
    caller falls back to the backup pool for !ok rows (slow path, rare).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ANY = pltpu.MemorySpace.ANY

BLOCK = 8


def _kernel(n: int, block: int):
    def kernel(idx_ref, data_ref, meta_ref, out_ref, ok_ref, mrows,
               sems, msems):
        b = pl.program_id(0)

        def _copies(j):
            row = idx_ref[b * block + j]
            return (
                pltpu.make_async_copy(data_ref.at[pl.ds(row, 1)],
                                      out_ref.at[pl.ds(j, 1)], sems.at[j]),
                pltpu.make_async_copy(meta_ref.at[pl.ds(row, 1)],
                                      mrows.at[pl.ds(j, 1)], msems.at[j]),
            )

        # Start ALL of the tile's row DMAs before waiting on any: the
        # per-lane semaphore array keeps `block` copies in flight, so the
        # gather is an overlapped HBM stream, not 2q dependent round trips.
        def start(j, _):
            for cp in _copies(j):
                cp.start()
            return 0

        def wait(j, _):
            for cp in _copies(j):
                cp.wait()
            return 0

        lax.fori_loop(0, block, start, 0)
        lax.fori_loop(0, block, wait, 0)
        meta = mrows[...]
        valid = jnp.logical_and(meta[:, :1] % 2 == 0, meta[:, 1:2] == 0)
        ok_ref[...] = valid.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def seqlock_gather(data: jax.Array, meta: jax.Array, idx: jax.Array,
                   *, block: int = BLOCK, interpret: bool = False):
    """data: uint32[n, k] (k lane-aligned); meta: uint32[n, 2] =
    (version, mark); idx: int32[q] in [0, n).  Returns (values uint32[q, k],
    ok int32[q, 1]) — ok=0 rows must take the slow path."""
    n, k = data.shape
    q = idx.shape[0]
    pad = (-q) % block
    if pad:
        idx = jnp.concatenate([idx, jnp.zeros((pad,), jnp.int32)])
    qq = q + pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(qq // block,),
        in_specs=[
            pl.BlockSpec(memory_space=_ANY),                  # data (HBM)
            pl.BlockSpec(memory_space=_ANY),                  # meta (HBM)
        ],
        out_specs=[
            pl.BlockSpec((block, k), lambda i, s: (i, 0)),    # values tile
            pl.BlockSpec((block, 1), lambda i, s: (i, 0)),    # ok tile
        ],
        scratch_shapes=[
            pltpu.VMEM((block, 2), jnp.uint32),
            pltpu.SemaphoreType.DMA((block,)),
            pltpu.SemaphoreType.DMA((block,)),
        ],
    )
    vals, ok = pl.pallas_call(
        _kernel(n, block),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qq, k), data.dtype),
            jax.ShapeDtypeStruct((qq, 1), jnp.int32),
        ],
        interpret=interpret,
    )(idx, data, meta)
    return vals[:q], ok[:q]
