"""repro.obs acceptance suite (ISSUE 9).

Two contracts, both tier-1:

  * OFF IS FREE — with BIGATOMIC_OBS unset/off the engine traces the exact
    pre-observability programs (zero new jit cache entries across a sweep)
    and the fused serving decode stays ONE dispatch per step; no host
    counter is ever recorded.

  * COUNTERS ARE DEFINITIONS — with BIGATOMIC_OBS=counters, every in-graph
    counter equals the `tests/oracle.TelemetryOracle` recount from the
    delivered batches/results BIT-EXACTLY, across the four lock-free
    strategies x {xla, pallas-interpret} engine kernels, including MCAS
    runs and distributed route-overflow lanes; and turning counters on
    never perturbs results (bit-equal to the off-mode run).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from oracle import (TableOracle, TelemetryOracle, TxnOracle, mixed_batch,
                    txn_batch)
from repro import atomics, obs
from repro.analysis import tracing
from repro.core import engine

STRATEGIES = ("seqlock", "indirect", "cached_wf", "cached_me")


def _sweep(spec, *, batches, seed):
    """Drive `batches` mixed batches through engine.apply, threading ctx.
    Returns (oracle, [(ops, live_result)], final logical table)."""
    p = spec.p_max
    oc = TableOracle(spec.n, spec.k, p)
    state, ctx = engine.init(spec), None
    rng = np.random.default_rng(seed)
    seen = []
    for _ in range(batches):
        ops = mixed_batch(rng, oc.ctx, p=p, n=spec.n, k=spec.k,
                          current=oc.data)
        ref = oc.step(ops)
        state, ctx, res, stats, _ = engine.apply(spec, state, ops, ctx)
        oc.check(result=res, ref=ref, msg="live vs oracle")
        seen.append((ops, res))
    return oc, seen, np.asarray(atomics.logical(spec, state))


# ---------------------------------------------------------------------------
# Off is free.
# ---------------------------------------------------------------------------

def test_off_returns_legacy_tuple_and_adds_zero_traces(monkeypatch):
    """BIGATOMIC_OBS=off: apply returns the classic 5-tuple and a whole
    sweep adds ZERO entries to the jitted round's cache — the telem pytree
    is None (an empty pytree), so the traced program is byte-identical to
    the pre-observability one."""
    monkeypatch.delenv("BIGATOMIC_OBS", raising=False)
    n, k, p = 32, 2, 16
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    oc = TableOracle(n, k, p)
    rng = np.random.default_rng(0)
    state, ctx = engine.init(spec), None
    for _ in range(2):          # warm both signatures: ctx=None, then LinkCtx
        ops = mixed_batch(rng, oc.ctx, p=p, n=n, k=k, current=oc.data)
        oc.step(ops)
        out = engine.apply(spec, state, ops, ctx)
        assert len(out) == 5, "off-mode apply must keep the legacy 5-tuple"
        state, ctx = out[0], out[1]
    with tracing.assert_max_new_traces(engine._apply, 0):
        for _ in range(4):
            ops = mixed_batch(rng, oc.ctx, p=p, n=n, k=k, current=oc.data)
            oc.step(ops)
            state, ctx, *_ = engine.apply(spec, state, ops, ctx)
    # off also means: no host counters, device counters all zero.
    assert all(v == 0 for v in obs.snapshot().values())


def test_counters_flag_flip_is_a_mode_not_a_retrace_hazard(monkeypatch):
    """Turning counters ON and OFF mid-process must never hit a stale
    trace: the telem argument's None-ness selects the program."""
    n, k, p = 16, 2, 8
    spec = atomics.AtomicSpec(n, k, "seqlock", p_max=p)
    ops = atomics.stores(np.arange(p, dtype=np.int32) % n,
                         np.ones((p, k), np.uint32), k=k)
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    out_on = engine.apply(spec, engine.init(spec), ops)
    assert len(out_on) == 5          # telem rides the call, not the return
    assert obs.snapshot()["engine.batches"] == 1
    monkeypatch.setenv("BIGATOMIC_OBS", "off")
    out_off = engine.apply(spec, engine.init(spec), ops)
    np.testing.assert_array_equal(np.asarray(out_on[2].success),
                                  np.asarray(out_off[2].success))
    assert obs.snapshot()["engine.batches"] == 1   # off run counted nothing


# ---------------------------------------------------------------------------
# Counters match the oracle recount, bit-exactly.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", ("xla", "pallas"))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_counters_match_oracle(monkeypatch, strategy, kernel):
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    monkeypatch.setenv("BIGATOMIC_ENGINE_KERNEL", kernel)
    obs.reset()
    # pallas runs interpret-mode on CPU: keep it small.
    n, p, batches = (64, 24, 6) if kernel == "xla" else (32, 12, 4)
    spec = atomics.AtomicSpec(n, 2, strategy, p_max=p)
    fused = engine.round_for(spec, mode=kernel) is not engine.linearize
    tel = TelemetryOracle(n)
    _, seen, _ = _sweep(spec, batches=batches, seed=sum(map(ord, strategy)))
    for ops, res in seen:
        tel.count_table_batch(ops, res, fused=fused)
    # quiescent reads: lock-free strategies never observe a torn cell.
    _, ok = engine.read(spec, engine.init(spec), np.arange(8, dtype=np.int32))
    tel.count_read(ok)
    snap = obs.snapshot()
    want = tel.counts()
    got = {name: snap[name] for name in want}
    assert got == want, {name: (got[name], want[name])
                         for name in want if got[name] != want[name]}


def test_counters_do_not_perturb_results(monkeypatch):
    """The counters program must compute the exact same table/results as
    the off program — counters observe, never steer."""
    spec = atomics.AtomicSpec(32, 2, "cached_wf", p_max=16)
    monkeypatch.setenv("BIGATOMIC_OBS", "off")
    _, seen_off, logical_off = _sweep(spec, batches=4, seed=42)
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    _, seen_on, logical_on = _sweep(spec, batches=4, seed=42)
    np.testing.assert_array_equal(logical_off, logical_on)
    for (_, a), (_, b) in zip(seen_off, seen_on):
        np.testing.assert_array_equal(np.asarray(a.value),
                                      np.asarray(b.value))
        np.testing.assert_array_equal(np.asarray(a.success),
                                      np.asarray(b.success))
    assert obs.snapshot()["engine.batches"] == 4


@pytest.mark.parametrize("strategy", ("seqlock", "cached_me"))
def test_mcas_counters_match_oracle(monkeypatch, strategy):
    from repro.txn import mcas as txn_mcas
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    n, k, t, w = 12, 2, 8, 3
    spec = atomics.AtomicSpec(n, k, strategy, p_max=64)
    rng = np.random.default_rng(7)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    state = atomics.init(spec, init)
    oracle = TxnOracle(n, k, initial=init)
    tel = TelemetryOracle(n)
    for _ in range(3):
        txns = txn_batch(rng, t=t, w=w, n=n, k=k, current=oracle.data)
        state, res = atomics.mcas(spec, state, txns)
        oracle.step_and_check(txns, result=res,
                              logical=atomics.logical(spec, state),
                              order=txn_mcas.linearization_order(res))
        tel.count_mcas(res)
    snap = obs.snapshot()
    want = tel.counts()
    got = {name: snap[name] for name in want}
    assert got == want, (got, want)
    assert snap["mcas.commits"] > 0      # the sweep must exercise commits
    assert snap["mcas.aborts"] > 0       # ... and real aborts


def test_dist_counters_match_oracle_including_overflow(monkeypatch):
    """Distributed route-overflow lanes count from the same claimed-order
    overflow mask the linearization oracle uses (single-device mesh; the
    multi-host variant rides tests/dist_checks.py in CI)."""
    from repro.core import distributed as dsb
    from repro.launch.mesh import make_mesh
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    n, k, pl, cap = 16, 2, 8, 3
    mesh = make_mesh((1,), ("shard",))
    dspec = dsb.DistSpec(atomics.AtomicSpec(n, k, "cached_me", p_max=64),
                         "shard", 1, pl, route_capacity=cap)
    p = dspec.p_global
    rng = np.random.default_rng(9)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    st = dsb.init_dist(mesh, dspec, init)
    tel = TelemetryOracle(n)
    oracle = TableOracle(n, k, p, initial=init)
    for _ in range(2):
        # all lanes write shard 0 => lanes beyond cap=3 overflow.
        ops = atomics.make_ops(
            np.full(p, atomics.STORE, np.int32),
            rng.integers(0, n, p).astype(np.int32),
            desired=rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32), k=k)
        order, ovf_ref = dsb.linearization_order(dspec, ops)
        st, ctx, res, ovf = dsb.apply(mesh, dspec, st, ops)
        np.testing.assert_array_equal(np.asarray(ovf), ovf_ref)
        oracle.step_and_check(ops, result=res, order=order,
                              overflow=ovf_ref, msg="dist overflow")
        tel.count_dist_batch(ovf_ref, dsb.collective_words(dspec))
    snap = obs.snapshot()
    want = tel.counts()
    got = {name: snap[name] for name in want}
    assert got == want, (got, want)
    assert snap["dist.route_overflow"] > 0


# ---------------------------------------------------------------------------
# Host-side counters (queue retry loop, serving engine).
# ---------------------------------------------------------------------------

def test_queue_counters_record_retry_pressure(monkeypatch):
    from repro.sync.queue import BigQueue
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    q = BigQueue(4, k=2, strategy="cached_me")
    ok = q.enqueue_batch(np.arange(6, dtype=np.uint32))   # 6 lanes, cap 4
    assert int(ok.sum()) == 4
    out, succ = q.dequeue_batch(6)                        # 4 items left
    assert int(succ.sum()) == 4
    snap = obs.snapshot()
    assert snap["queue.enq"] == 4
    assert snap["queue.deq"] == 4
    assert snap["queue.enq_full"] >= 2     # the two over-capacity lanes
    assert snap["queue.deq_empty"] >= 2    # the two over-drain lanes
    assert snap["queue.rounds"] >= 2


# -- serving: share the (expensive) reduced model across both tests --------

_SERVING = {}


def _serving_cfg_params():
    if not _SERVING:
        from repro.configs import get_config
        from repro.models.transformer import init_params
        cfg = dataclasses.replace(get_config("deepseek_7b", reduced=True),
                                  param_dtype="float32",
                                  compute_dtype="float32")
        _SERVING["cfg"] = cfg
        _SERVING["params"] = init_params(cfg, jax.random.PRNGKey(0))
    return _SERVING["cfg"], _SERVING["params"]


def _serve_two(cfg, params):
    from repro.serving import Request, ServingEngine
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, max_batch=2, n_pages=24, page_size=4,
                        max_pages_per_seq=8)
    for rid, plen in enumerate((11, 6)):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab, plen)
                                     .astype(np.int32),
                           max_new_tokens=5))
    eng.run_to_completion()
    return eng


def test_serving_off_keeps_single_dispatch_per_decode_step(monkeypatch):
    """ISSUE 9 acceptance: with BIGATOMIC_OBS=off the fused decode path is
    untouched — exactly ONE jitted dispatch per shared decode step and
    zero observability state recorded anywhere."""
    monkeypatch.delenv("BIGATOMIC_OBS", raising=False)
    obs.reset()
    cfg, params = _serving_cfg_params()
    eng = _serve_two(cfg, params)
    # both slots decode together for 4 fused steps, 1 dispatch each
    assert eng.dispatch_count == 4, eng.dispatch_count
    assert all(v == 0 for v in obs.snapshot().values())


def test_serving_counters_mirror_dispatch_accounting(monkeypatch):
    monkeypatch.setenv("BIGATOMIC_OBS", "counters")
    obs.reset()
    cfg, params = _serving_cfg_params()
    eng = _serve_two(cfg, params)
    snap = obs.snapshot()
    assert snap["serving.admitted"] == 2
    assert snap["serving.retired"] == 2
    assert snap["serving.decode_steps"] == 4
    assert snap["serving.dispatches"] == eng.dispatch_count == 4
