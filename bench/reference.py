"""The plain reference for the table cells, in vectorised numpy.

It imports nothing of the program and takes nothing it made: the table's
first words come from `gen.cell_words`, as the program's do.  A batch is
applied as its ops would apply one at a time in lane order
(linearizability, with the lane order as the linearization), which is the
program's stated semantics (`engine.apply_ops_reference`).

Two weakenings serve as the controls that `correct` must reject:

  half_words  every word is held in 16 bits (the word width below the
              configuration's 32), so values read back lose their high
              halves;
  snapshot    every op of a batch sees the state from before the batch
              (no op sees an earlier lane's write in the same batch): the
              shortcut of skipping the serialising slow path.
"""

from __future__ import annotations

import numpy as np

from bench import gen

FULL = 0xFFFFFFFF
HALF = 0xFFFF


def _segments(key: np.ndarray):
    """Stable sort by key; (order, sorted keys, index of each sorted
    position's segment start, mask of segment ends)."""
    order = np.argsort(key, kind="stable")
    s = key[order]
    start = np.ones(len(s), bool)
    start[1:] = s[1:] != s[:-1]
    first = np.maximum.accumulate(np.where(start, np.arange(len(s)), 0))
    end = np.ones(len(s), bool)
    end[:-1] = start[1:]
    return order, s, first, end


def _last_before(flag: np.ndarray, first: np.ndarray, *, inclusive: bool):
    """For each sorted position, the last position in its segment (before
    it, or up to it when `inclusive`) where `flag` holds; -1 where none."""
    idx = np.where(flag, np.arange(len(flag)), -1)
    last = np.maximum.accumulate(idx) if len(idx) else idx
    if not inclusive:
        last = np.concatenate([[-1], last[:-1]])
    return np.where(last >= first, last, -1)


class TableRef:
    """A table of k-word cells under LOAD / STORE, holding only the
    cells that some batch names.  `slots` maps a batch's name to its slot
    array; `apply` takes a batch by that name."""

    def __init__(self, seed: int, slots: dict, k: int, codes: dict, *,
                 mask: int = FULL, snapshot: bool = False):
        names = list(slots)
        every = np.concatenate([np.asarray(slots[b]) for b in names])
        self.cells, inv = np.unique(every.astype(np.int64),
                                    return_inverse=True)
        cuts = np.cumsum([len(slots[b]) for b in names])[:-1]
        self.loc = dict(zip(names, np.split(inv, cuts)))
        self.mask = np.uint32(mask)
        self.data = gen.cell_words(seed, self.cells, k) & self.mask
        self.version = np.zeros(len(self.cells), np.uint32)
        self.codes, self.snapshot = codes, snapshot

    def apply(self, batch, kind, desired):
        """One batch; returns (value[p, k], success[p])."""
        c = self.codes
        kind = np.asarray(kind)
        if np.isin(kind, [c["LOAD"], c["STORE"], c["IDLE"]],
                   invert=True).any():
            raise NotImplementedError("the reference takes LOAD and STORE")
        desired = np.asarray(desired) & self.mask
        loc = self.loc[batch]
        active = kind != c["IDLE"]
        if not (kind == c["STORE"]).any():
            value = np.where(active[:, None], self.data[loc], 0)
            return value.astype(desired.dtype), active
        loc = np.where(active, loc, -1)
        order, s, first, end = _segments(loc)
        kd, des = kind[order], desired[order]
        write = kd == c["STORE"]
        prev = _last_before(write, first, inclusive=False)
        pre = self.data[np.maximum(s, 0)]
        if not self.snapshot:
            pre = np.where((prev >= 0)[:, None], des[np.maximum(prev, 0)], pre)
        act = s >= 0
        value = np.zeros_like(desired)
        success = np.zeros(len(kind), bool)
        value[order] = np.where(act[:, None], pre, 0)
        success[order] = act
        last = _last_before(write, first, inclusive=True)
        fin = end & act & (last >= 0)
        self.data[s[fin]] = des[last[fin]]
        np.add.at(self.version, s[write & act], np.uint32(2))
        return value, success

    def read(self, cells):
        """(values, versions) of `cells`, which some batch named."""
        loc = np.searchsorted(self.cells, cells)
        if (loc >= len(self.cells)).any() or (self.cells[loc] != cells).any():
            raise ValueError("a cell that no batch named")
        return self.data[loc], self.version[loc]
