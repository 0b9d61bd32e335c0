"""The roofline byte count on a hand-worked batch."""

import numpy as np

from bench.roofline import table_bytes

CODES = {"LOAD": 0, "STORE": 1, "CAS": 2, "IDLE": 3, "SC": 5}


def test_hand_worked_batch():
    # k = 4: each active lane moves inputs (kind 4 + slot 4 + comparand 16
    # + desired 16 = 40 B), results (value 16 + flag 1 = 17 B) and reads
    # its cell (16 + version 4 = 20 B): 77 B.  A write adds 20 B.
    kind = np.asarray([0, 1, 2, 2, 3, 5])
    success = np.asarray([1, 1, 1, 0, 0, 0], bool)
    # active: 5 lanes (IDLE left out); writes: STORE, the CAS that
    # succeeded (the failed CAS and failed SC write nothing).
    assert table_bytes(kind, success, 4, CODES) == 5 * 77 + 2 * 20


def test_all_loads():
    p = 8192
    assert table_bytes(np.zeros(p, int), np.ones(p, bool), 4,
                       CODES) == p * 77
