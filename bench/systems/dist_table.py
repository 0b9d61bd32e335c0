"""A table of k-word big atomics sharded over the cell's chips, one shard a
chip in contiguous blocks of records, driven through
`repro.atomics.dist.apply`.

Lane i of a batch issues from the chip of shard i // (lanes / chips).  The
sharded round applies each record's ops at its owner in (source chip,
lane) order, which is the batch's lane order, so the table cell's
reference (`bench/reference.TableRef`) holds unchanged.  Nothing of the
table is gathered onto one chip or into the host: each shard is built on
its own chip from the seed, and read back after the window shard by shard
(`dist.gather` for the records some batch named, `dist.count_cells` in
blocks for the rest).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import numpy as np

from bench import gen
from bench.systems import table
from repro import atomics
from repro.launch.mesh import make_mesh

CODES, WRITES, Control = table.CODES, table.WRITES, table.Control
TINY = {"n_cells": 4096, "lanes": 64}
BLOCK = 1 << 22                # records a shard checks at a time


class Spec(NamedTuple):
    mesh: object
    dspec: object


def entry(spec, state, ops):
    """The call the window times, as a caller that threads its state makes
    it: (state', ctx', ApplyResult, overflow)."""
    return atomics.dist.apply(spec.mesh, spec.dspec, state, ops,
                              donate=True)


def change_record(state, record: int, field: str):
    """`state` with one record changed: its first word ("data") or its
    version ("version")."""
    local = state.local
    shard, i = divmod(record, local.version.shape[1])
    if field == "data":
        local = local._replace(data=local.data.at[shard, i, 0].add(1))
    else:
        local = local._replace(version=local.version.at[shard, i].set(2))
    return state._replace(local=local)


def untouched_mismatches(spec: Spec, seed: int, state, touched,
                         block: int = BLOCK) -> int:
    """Records that no batch named (`touched`: sorted global ids of those
    that some batch named) whose logical value is not the one they started
    with (`gen.cell_words`) or whose version is not 0, counted on each
    shard in blocks of `block` records."""
    jnp = jax.numpy
    k = spec.dspec.inner.k

    def changed(cells, values, versions, touched):
        start = gen.cell_words(seed, cells, k, jnp)
        bad = jnp.any(values != start, axis=1) | (versions != 0)
        pos = jnp.minimum(jnp.searchsorted(touched, cells),
                          touched.shape[0] - 1)
        return bad & (touched[pos] != cells)

    return atomics.dist.count_cells(spec.mesh, spec.dspec, state, changed,
                                    jnp.asarray(touched, jnp.int32),
                                    block=block)


class Cell(table.Cell):
    """config: n_cells, k_words, lanes, strategy, shards (one a device of
    `devices`)."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        if config["shards"] != len(devices):
            raise ValueError(f"{config['shards']} shards, "
                             f"{len(devices)} devices")
        super().__init__(config, traffic, seed, devices)

    def _build(self):
        d = len(self.devices)
        mesh = make_mesh((d,), ("shard",), devices=self.devices)
        self.dist = Spec(mesh, atomics.DistSpec(self.spec, "shard", d,
                                                self.spec.p_max // d))
        seed, k = self.seed, self.k
        return jax.block_until_ready(atomics.dist.init_dist(
            mesh, self.dist.dspec,
            lambda cells: gen.cell_words(seed, cells, k, jax.numpy)))

    def call(self, b: int):
        self.state, _, res, _ = entry(self.dist, self.state, self.ops[b])
        return res.value, res.success

    def readback(self, history):
        """After the window, shard by shard: the touched records' values
        and versions, and the count of untouched records that changed."""
        cells = self.touched(history)
        vals, vers = jax.device_get(atomics.dist.gather(
            self.dist.mesh, self.dist.dspec, self.state, cells))
        return (cells, np.asarray(vals), np.asarray(vers),
                untouched_mismatches(self.dist, self.seed, self.state,
                                     cells))
