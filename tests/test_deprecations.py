"""Deprecation contract (ISSUE 3 satellite): every v1 shim emits a
`DeprecationWarning` EXACTLY once per process — so tier-1 stays readable —
and keeps computing correct results.  Internal code paths (sync wrappers,
serving, queues) never route through the warning shims, so a default tier-1
run is warning-free."""

import warnings

import numpy as np
import pytest

from repro.core import bigatomic as ba
from repro.core import cachehash as ch
from repro.core import deprecation
from repro.core import distributed as dsb
from repro.core import engine
from repro.core import semantics as sem
from repro.launch.mesh import make_mesh
from repro.sync import llsc


def _catch(fn):
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = fn()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    return dep, out


def _call_apply_sync():
    state = ba.init(2, 2, "cached_me", p_max=8)
    ctx = llsc.init_ctx(2, 2)
    ops = llsc.make_sync_batch(np.full(2, llsc.LL, np.int32),
                               np.zeros(2, np.int32), k=2)
    return llsc.apply_sync(state, ctx, ops, strategy="cached_me", k=2)


def _call_apply_ops():
    state = ba.init(2, 2, "cached_me", p_max=8)
    ops = engine.loads([0, 1], k=2)
    return ba.apply_ops(state, ops, strategy="cached_me", k=2)


def _call_apply_hash_ops():
    from repro.core.specs import HashSpec
    state = ch.init_hash(HashSpec(4, vw=1, strategy="cached_me", p_max=8))
    ops = ch.make_hash_ops(np.asarray([engine.FIND], np.int32),
                           np.asarray([3], np.uint32), vw=1)
    return ch.apply_hash_ops(state, ops, strategy="cached_me", inline=True,
                             vw=1)


@pytest.mark.parametrize("name,call", [
    ("sync.llsc.apply_sync", _call_apply_sync),
    ("core.bigatomic.apply_ops", _call_apply_ops),
    ("core.cachehash.apply_hash_ops", _call_apply_hash_ops),
], ids=lambda x: x if isinstance(x, str) else "")
def test_shims_warn_exactly_once(name, call):
    deprecation.reset(name)
    first, _ = _catch(call)
    assert len(first) == 1, [str(w.message) for w in first]
    assert "deprecated" in str(first[0].message)
    second, _ = _catch(call)
    assert not second, "shim warned twice"


def test_internal_sync_wrappers_are_warning_free():
    """ll/sc/validate (and everything else repro.sync routes) go through
    atomics.apply directly — no DeprecationWarning ever."""
    state = ba.init(2, 2, "cached_me", p_max=8)
    ctx = llsc.init_ctx(1, 2)

    def drive():
        c, _ = llsc.ll(state, ctx, [0], strategy="cached_me", k=2)
        st, c, succ = llsc.sc(state, c, [0], np.ones((1, 2), np.uint32),
                              strategy="cached_me", k=2)
        llsc.validate(st, c, [0], strategy="cached_me", k=2)
        return succ

    warned, succ = _catch(drive)
    assert not warned, [str(w.message) for w in warned]
    assert bool(np.asarray(succ)[0])


def test_distributed_shims_warn_once_and_still_work():
    mesh = make_mesh((1,), ("shard",))
    n, k, pl = 4, 2, 4
    deprecation.reset("core.distributed.init_sharded")
    deprecation.reset("core.distributed.make_apply")
    w_init, table = _catch(lambda: dsb.init_sharded(mesh, "shard", n, k))
    assert len(w_init) == 1
    w_make, apply_ops = _catch(lambda: dsb.make_apply(mesh, "shard", n, k,
                                                      pl))
    assert len(w_make) == 1
    again, _ = _catch(lambda: dsb.init_sharded(mesh, "shard", n, k))
    assert not again

    rng = np.random.default_rng(0)
    ops = sem.random_batch(rng, p=pl, n=n, k=k, update_frac=0.5)
    table, res, ovf = apply_ops(table, ops)
    ref_d, ref_v, ref_res, dropped = dsb.reference_apply(
        np.zeros((n, k), np.uint32), np.zeros(n, np.uint32), ops,
        n_shards=1, p_local=pl)
    assert int(ovf) == len(dropped) == 0
    np.testing.assert_array_equal(np.asarray(table.data), ref_d)
    np.testing.assert_array_equal(np.asarray(res.success), ref_res.success)
