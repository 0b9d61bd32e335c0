"""Bytes that a batch of table ops needs to move, whatever implements it.

Per active lane: its inputs (kind and slot words, the k-word comparand and
the k-word desired value), its results (the k-word value and a one-byte
success flag), and a read of its cell's k words and version word.  Per
lane that writes (STORE, or CAS / SC that succeeds): a write of the cell's
k words and version word.  Nothing is counted for how an implementation
reaches a cell (the 128-lane windows the engine-round kernels DMA today),
so the count stays the same across implementations.  The ops do no
arithmetic to speak of, so the roofline that bounds them is HBM bytes.
"""

from __future__ import annotations

import numpy as np

WORD = 4


def table_bytes(kind, success, k: int, codes: dict) -> int:
    kind = np.asarray(kind)
    success = np.asarray(success, bool)
    active = kind != codes["IDLE"]
    writes = (kind == codes["STORE"]) | (
        np.isin(kind, [codes[m] for m in ("CAS", "SC") if m in codes])
        & success)
    per_lane = (2 + 2 * k) * WORD + (k * WORD + 1) + (k + 1) * WORD
    return int(active.sum()) * per_lane + int((writes & active).sum()) * (
        k + 1) * WORD
