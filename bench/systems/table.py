"""A table of k-word big atomics, driven through `repro.atomics.apply`."""

from __future__ import annotations

import jax
import numpy as np

from bench import gen, reference
from repro import atomics

CODES = {"LOAD": atomics.LOAD, "STORE": atomics.STORE, "IDLE": atomics.IDLE}
WRITES = ("STORE",)
TINY = {"n_cells": 4096, "lanes": 64}


def entry(spec, state, ops):
    """The call the window times, as a caller that threads its state makes
    it: (state', ctx', ApplyResult, ApplyStats, Traffic)."""
    return atomics.apply(spec, state, ops, donate=True)


def change_record(state, record: int, field: str):
    """`state` with one record changed: its first word ("data") or its
    version ("version")."""
    if field == "data":
        return state._replace(data=state.data.at[record, 0].add(1))
    return state._replace(version=state.version.at[record].set(2))


def _untouched_mismatches(spec, seed: int, state, touched) -> int:
    """On the device, over the whole table: cells that no batch named whose
    logical value is not the one they started with (`gen.cell_words`) or
    whose version is not 0."""
    jnp = jax.numpy

    def count(state, touched):
        cells = jnp.arange(spec.n, dtype=jnp.uint32)
        start = gen.cell_words(seed, cells, spec.k, jnp)
        bad = (jnp.any(atomics.logical(spec, state) != start, axis=1)
               | (state.version != 0))
        named = jnp.zeros(spec.n, bool).at[touched].set(True)
        return jnp.sum(bad & ~named)
    return int(jax.jit(count)(state, touched))


def _build(spec, seed: int):
    """The table on the device, in one jitted call from the seed."""
    def make():
        cells = jax.numpy.arange(spec.n, dtype=jax.numpy.uint32)
        return atomics.init(spec, gen.cell_words(seed, cells, spec.k,
                                                 jax.numpy))
    return jax.jit(make)()


class Cell:
    """config: n_cells, k_words, lanes, strategy.  The table lives on the
    first of `devices`."""

    codes = CODES

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        n, k, p = config["n_cells"], config["k_words"], config["lanes"]
        self.seed, self.k, self.devices = seed, k, devices
        self.spec = atomics.AtomicSpec(n, k, config["strategy"], p_max=p)
        pool = gen.draw(traffic, space=n, lanes=p, width=k, seed=seed,
                        codes=CODES)
        zeros = np.zeros((p, k), np.uint32)
        self.ops = [atomics.OpBatch(pool.kind[b],
                                    pool.index[b].astype(np.int32), zeros,
                                    pool.value[b])
                    for b in range(len(pool))]
        self.window_first = 0          # the pool's batches come first
        self.state = self._build()
        self.setup_history: list = []
        self.setup_results: list = []

    def _build(self):
        with jax.default_device(self.devices[0]):
            return jax.block_until_ready(_build(self.spec, self.seed))

    def lanes(self, b: int) -> int:
        return len(self.ops[b].kind)

    def call(self, b: int):
        """Submit batch b; returns its results, still on the device."""
        self.state, _, res, _, _ = entry(self.spec, self.state, self.ops[b])
        return res.value, res.success

    def counters(self, host) -> dict:
        return {}

    def failed(self, host) -> int:
        return 0

    def touched(self, history) -> np.ndarray:
        return np.unique(np.concatenate(
            [self.ops[b].slot for b in sorted(set(history))]))

    def readback(self, history):
        """After the window: the touched cells' values and versions, and
        the count of untouched cells that changed."""
        cells = self.touched(history)
        idx = jax.numpy.asarray(cells)
        vals = atomics.logical(self.spec, self.state)[idx]
        out = jax.device_get((cells, vals, self.state.version[idx]))
        return (*out, _untouched_mismatches(self.spec, self.seed,
                                            self.state, idx))

    def free(self) -> None:
        self.state = None

    def reference(self, history, **weaken):
        return reference.TableRef(
            self.seed, {b: self.ops[b].slot for b in sorted(set(history))},
            self.k, CODES, **weaken)

    def check(self, history, results, final) -> dict:
        """Numbers compared, each (value, limit): ops whose value or
        success differs from the reference's; after the window, touched
        cells whose value or version differs, and untouched cells that
        changed at all."""
        ref = self.reference(history)
        bad_ops = 0
        for b, (value, success) in zip(history, results):
            ops = self.ops[b]
            want_v, want_s = ref.apply(b, ops.kind, ops.desired)
            bad_ops += int(np.sum(np.any(np.asarray(value) != want_v, axis=1)
                                  | (np.asarray(success) != want_s)))
        cells, vals, vers, untouched = final
        want_v, want_ver = ref.read(cells)
        bad_cells = int(np.sum(np.any(np.asarray(vals) != want_v, axis=1)
                               | (np.asarray(vers) != want_ver)))
        return {"op_mismatches": (bad_ops, 0),
                "cell_mismatches": (bad_cells, 0),
                "untouched_mismatches": (untouched, 0)}


class Control(Cell):
    """The reference, weakened, in the program's place."""

    def __init__(self, config, traffic, seed, devices, weaken: dict):
        self._weaken = weaken
        self._ref = None
        super().__init__(config, traffic, seed, devices)

    def _build(self):
        return None

    def call(self, b: int):
        if self._ref is None:
            self._ref = self.reference(range(len(self.ops)), **self._weaken)
        ops = self.ops[b]
        return self._ref.apply(b, ops.kind, ops.desired)

    def readback(self, history):
        cells = self.touched(history)
        vals, vers = self._ref.read(cells)
        return cells, vals, vers, 0
