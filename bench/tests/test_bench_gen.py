"""The traffic generator and the cell words."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen

CODES = {"LOAD": 0, "STORE": 1}


def _pool(traffic, seed):
    return gen.draw(traffic, space=1 << 16, lanes=512, width=2, seed=seed,
                    codes=CODES)


@pytest.mark.parametrize("keys", ["zipf", "uniform"])
def test_pool_repeats_from_its_seed(keys):
    traffic = {"mix": {"LOAD": 0.5, "STORE": 0.5}, "keys": keys,
               "theta": 0.99, "pool_batches": 3}
    a, b = _pool(traffic, 2 ** 33 + 1), _pool(traffic, 2 ** 33 + 1)
    c = _pool(traffic, 2 ** 33 + 2)
    for x, y, z in ((a.kind, b.kind, c.kind), (a.index, b.index, c.index),
                    (a.value, b.value, c.value)):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)


def test_op_kinds_follow_the_mix():
    mix = {"LOAD": 0.95, "STORE": 0.05}
    pool = gen.draw({"mix": mix, "keys": "uniform", "pool_batches": 16},
                    space=1 << 10, lanes=8192, width=1, seed=5,
                    codes={"LOAD": 7, "STORE": 8})
    assert set(np.unique(pool.kind).tolist()) == {7, 8}
    stores = (pool.kind == 8).sum(axis=1)
    sd = np.sqrt(8192 * 0.05 * 0.95)
    assert abs(stores.mean() - 8192 * 0.05) < 4 * sd / 4
    assert len(set(stores.tolist())) > 1   # drawn per lane, not fixed
    with pytest.raises(ValueError):
        gen.draw({"mix": {"LOAD": 0.5}, "keys": "uniform",
                  "pool_batches": 1}, space=4, lanes=4, width=1, seed=1,
                 codes={"LOAD": 7})


def test_zipf_ranks_are_drawn_independently():
    """Each lane's rank is YCSB's for its own uniform variate: the hottest
    ranks' counts follow their probabilities and vary from batch to batch."""
    zipf = gen.Zipf(1 << 21, 0.99)
    rng = gen.rng_for(2 ** 33 + 3, 1)
    batches = [zipf.draw(rng, 8192) for _ in range(64)]
    assert all(b.min() >= 0 and b.max() < 1 << 21 for b in batches)
    for r, p in ((0, 1 / zipf.zetan), (1, 0.5 ** 0.99 / zipf.zetan)):
        counts = np.asarray([np.sum(b == r) for b in batches])
        sd = np.sqrt(8192 * p * (1 - p))
        assert abs(counts.mean() - 8192 * p) < 4 * sd / np.sqrt(64)
        assert 0.5 * sd < counts.std() < 2 * sd


def test_spread_is_a_bijection():
    ranks = np.arange(1 << 12)
    out = gen.spread(ranks, 1 << 12, 0x9E3779B1, 77)
    assert sorted(out.tolist()) == list(range(1 << 12))


def test_cell_words_agree_on_host_and_device():
    cells = np.arange(0, 1 << 12, 7)
    host = gen.cell_words(2 ** 40 + 3, cells, 4)
    dev = np.asarray(gen.cell_words(2 ** 40 + 3, jnp.asarray(cells), 4, jnp))
    np.testing.assert_array_equal(host, dev)
    assert host.dtype == np.uint32 and len(np.unique(host)) > 0.99 * host.size
