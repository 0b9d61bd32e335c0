"""The reference agrees with the program at a tiny size, with the engine's
Pallas kernels in interpret mode."""

import jax
import numpy as np
import pytest

from bench import gen, reference
from bench.systems import table as table_sys
from repro import atomics
from repro.core import engine


@pytest.fixture
def pallas(monkeypatch):
    monkeypatch.setenv("BIGATOMIC_ENGINE_KERNEL", "pallas")


@pytest.mark.parametrize("mix,keys", [
    ({"LOAD": 0.5, "STORE": 0.5}, "zipf"),
    ({"LOAD": 1.0}, "uniform"),
    ({"LOAD": 0.9, "STORE": 0.1}, "uniform"),
])
@pytest.mark.parametrize("k", [4, 8])
def test_table_reference_matches_program(pallas, mix, keys, k):
    n, p, seed = 512, 32, 2 ** 33 + 9
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    state = table_sys._build(spec, seed)
    pool = gen.draw({"mix": mix, "keys": keys, "theta": 0.99,
                     "pool_batches": 4}, space=n, lanes=p, width=k,
                    seed=seed, codes=table_sys.CODES)
    ref = reference.TableRef(seed, dict(enumerate(pool.index)), k,
                             table_sys.CODES)
    data = gen.cell_words(seed, np.arange(n), k)
    version = np.zeros(n, np.uint32)
    ctx = atomics.init_ctx(p, k)
    for b in range(len(pool)):
        ops = atomics.OpBatch(pool.kind[b], pool.index[b].astype(np.int32),
                              np.zeros((p, k), np.uint32), pool.value[b])
        state, _, res, _, _ = atomics.apply(spec, state, ops)
        want_v, want_s = ref.apply(b, ops.kind, ops.desired)
        np.testing.assert_array_equal(np.asarray(res.value), want_v)
        np.testing.assert_array_equal(np.asarray(res.success), want_s)
        data, version, ctx, oracle = engine.apply_ops_reference(
            data, version, ctx, ops)
        np.testing.assert_array_equal(oracle.value, want_v)
    np.testing.assert_array_equal(
        np.asarray(atomics.logical(spec, state))[ref.cells], ref.data)
    np.testing.assert_array_equal(np.asarray(state.version)[ref.cells],
                                  ref.version)
    untouched = np.setdiff1d(np.arange(n), ref.cells)
    np.testing.assert_array_equal(np.asarray(state.version)[untouched], 0)


def test_weakened_references_differ():
    codes = table_sys.CODES
    kind = np.asarray([codes["STORE"], codes["LOAD"]], np.int32)
    slots = {0: np.asarray([3, 3])}
    desired = np.full((2, 2), 0xABCD1234, np.uint32)
    full = reference.TableRef(1, slots, 2, codes).apply(0, kind, desired)
    snap = reference.TableRef(1, slots, 2, codes, snapshot=True).apply(
        0, kind, desired)
    half = reference.TableRef(1, slots, 2, codes,
                              mask=reference.HALF).apply(0, kind, desired)
    np.testing.assert_array_equal(full[0][1], desired[0])
    assert not np.array_equal(snap[0][1], desired[0])
    np.testing.assert_array_equal(half[0][1], desired[0] & 0xFFFF)
