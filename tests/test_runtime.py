"""Fault-tolerance tests: atomic checkpoints, bit-identical preemption
resume, straggler watchdog logic, elastic resharding (subprocess with 8
placeholder devices), the oversubscribed multi-stream executor (DESIGN.md
§9), deterministic data pipeline."""

import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (latest_step, list_steps, restore_checkpoint,
                              save_checkpoint)
from repro.configs import get_config
from repro.configs.shapes import SHAPES, reduced_shape
from repro.data import DataPipeline, synthetic_batch
from repro.runtime import PreemptionGuard, StragglerWatchdog, mesh_plan
from repro.runtime.stragglers import StragglerPlan


# ---------------------------------------------------------------------------
# checkpoint atomicity
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    state = {"a": jnp.arange(12.0).reshape(3, 4),
             "b": {"c": jnp.int32(7)}}
    d = str(tmp_path)
    save_checkpoint(d, 10, state, meta={"next_step": 10})
    # a fake interrupted write: staging dir with no manifest
    os.makedirs(os.path.join(d, ".staging_dead"), exist_ok=True)
    # and a torn final dir missing its manifest
    os.makedirs(os.path.join(d, "step_00000020"), exist_ok=True)
    assert list_steps(d) == [10]                 # torn ckpt invisible
    got, meta = restore_checkpoint(d, 10, state)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(state["a"]))
    assert int(got["b"]["c"]) == 7
    assert meta["next_step"] == 10


def test_preemption_guard_flag():
    with PreemptionGuard() as g:
        assert not g.should_stop
        g.request_stop()
        assert g.should_stop


def test_preemption_guard_restores_handlers_on_enter_failure():
    """A failed __enter__ (handler i raises) must roll back handlers
    0..i-1 — a guard that never activated may not leak signal handlers."""
    marker = lambda signum, frame: None          # noqa: E731
    old = signal.signal(signal.SIGTERM, marker)
    try:
        with pytest.raises((ValueError, OSError)):
            # 2nd entry is not a valid signal: installing it raises AFTER
            # SIGTERM's handler was already swapped
            with PreemptionGuard(signals=(signal.SIGTERM, 10 ** 6)):
                pytest.fail("enter must not succeed")
        assert signal.getsignal(signal.SIGTERM) is marker
        # and a clean enter/exit round-trips the handler too
        with PreemptionGuard(signals=(signal.SIGTERM,)):
            assert signal.getsignal(signal.SIGTERM) is not marker
        assert signal.getsignal(signal.SIGTERM) is marker
    finally:
        signal.signal(signal.SIGTERM, old)


def test_preempt_resume_bit_identical(tmp_path):
    """Train 8 steps straight vs 4 steps -> 'preempt' -> resume 4 more:
    final params must be bit-identical."""
    from repro.launch.train import train
    cfg = get_config("deepseek_7b", reduced=True)
    shape = reduced_shape(SHAPES["train_4k"])
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")

    p_full, _, _ = train(cfg, shape, steps=8, ckpt_dir=d1, ckpt_every=100,
                         log_every=0)

    class StopAt:
        def __init__(self, n):
            self.n = n
            self.seen = 0

        @property
        def should_stop(self):
            self.seen += 1
            return self.seen > self.n

    train(cfg, shape, steps=8, ckpt_dir=d2, ckpt_every=100, log_every=0,
          guard=StopAt(4))
    assert latest_step(d2) == 5          # preempted after finishing step 5
    p_res, _, _ = train(cfg, shape, steps=8, ckpt_dir=d2, ckpt_every=100,
                        log_every=0)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------

def test_straggler_flags_after_patience():
    w = StragglerWatchdog(n_hosts=4, threshold=1.5, patience=3,
                          spares=["spare0"])
    for _ in range(2):
        plan = w.observe([1.0, 1.0, 1.0, 5.0])
        assert plan.flagged == []                # patience not reached
    plan = w.observe([1.0, 1.0, 1.0, 5.0])
    assert plan.flagged == [3]
    assert plan.swap == {3: "spare0"}
    assert plan.shrink == []
    # next flagged host has no spare left -> shrink plan
    w2 = StragglerWatchdog(n_hosts=2, patience=1)
    plan = w2.observe([1.0, 9.0])
    assert plan.shrink == [1]


def test_straggler_blip_does_not_flag():
    w = StragglerWatchdog(n_hosts=3, patience=2)
    w.observe([1.0, 1.0, 1.0])
    plan = w.observe([1.0, 1.0, 30.0])           # one-off blip
    assert plan.flagged == []
    plan = w.observe([1.0, 1.0, 1.0])
    assert plan.flagged == []                    # EWMA recovered


def test_mesh_plan_reports_dropped_devices():
    """Surviving-device counts that don't factorize are REPORTED, never
    silently truncated (a 7-survivor cluster quietly running on 4 devices
    is a capacity bug)."""
    assert mesh_plan(8, model_parallel=2) == (4, 2, 8, 0)
    assert mesh_plan(7, model_parallel=4) == (7, 1, 7, 0)
    p = mesh_plan(7, model_parallel=1, global_batch=4)
    assert (p.data, p.model, p.used, p.dropped) == (1, 1, 1, 6)
    p = mesh_plan(6, model_parallel=4, global_batch=4)
    assert (p.used, p.dropped) == (2, 4)
    assert mesh_plan(6, model_parallel=4).dropped == 0


# ---------------------------------------------------------------------------
# the oversubscribed multi-stream executor (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _synth_streams(n_streams, *, n, k, width, n_batches, seed0=50):
    from repro.runtime import SyntheticStream
    return [SyntheticStream(f"s{i}", seed=seed0 + i, n=n, k=k, width=width,
                            n_batches=n_batches, hot_cells=3, hot_frac=0.25)
            for i in range(n_streams)]


def test_executor_oversubscribed_local_matches_oracle():
    """3 streams, in-flight budget 4 on 1 slot: the journaled interleaving
    replays through ONE sequential oracle and the final table matches."""
    from repro import atomics
    from repro.core import engine
    from repro.runtime import Executor, LocalTarget
    sys.path.insert(0, os.path.dirname(__file__))
    from oracle import replay_executor_history

    n, k, width = 24, 2, 8
    rng = np.random.default_rng(0)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    target = LocalTarget(atomics.AtomicSpec(n, k, "seqlock", p_max=64), init)
    streams = _synth_streams(3, n=n, k=k, width=width, n_batches=5)
    ex = Executor(target, streams, slots=1, oversubscription=4)
    rep = ex.run()
    assert rep["issues"] == 15 and ex.budget == 4
    oracle = replay_executor_history(n, k, [width] * 3, ex.history,
                                     initial=init)
    np.testing.assert_array_equal(
        oracle.data, np.asarray(engine.logical(target.spec, target.state)))
    np.testing.assert_array_equal(oracle.version,
                                  np.asarray(target.state.version))


def test_executor_preempt_checkpoint_resume(tmp_path):
    """A preempt fault mid-run drains + checkpoints to disk; a FRESH
    executor (new process stand-in) resumes from it and finishes with the
    table bit-identical to an uninterrupted run."""
    from repro import atomics
    from repro.core import engine
    from repro.runtime import Executor, Fault, FaultInjector, LocalTarget

    n, k, width = 24, 2, 8
    spec = atomics.AtomicSpec(n, k, "seqlock", p_max=64)
    rng = np.random.default_rng(1)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)

    ref = LocalTarget(spec, init)
    Executor(ref, _synth_streams(2, n=n, k=k, width=width,
                                 n_batches=6)).run()
    want = np.asarray(engine.logical(spec, ref.state))

    d = str(tmp_path)
    t1 = LocalTarget(spec, init)
    ex1 = Executor(t1, _synth_streams(2, n=n, k=k, width=width, n_batches=6),
                   injector=FaultInjector([Fault(round=3, kind="preempt")]),
                   checkpoint_dir=d)
    rep1 = ex1.run()
    assert rep1["stopped"] and latest_step(d) is not None

    t2 = LocalTarget(spec, init)                 # fresh process stand-in
    ex2 = Executor(t2, _synth_streams(2, n=n, k=k, width=width, n_batches=6),
                   checkpoint_dir=d)
    ex2.resume()
    rep2 = ex2.run()
    assert not rep2["stopped"]
    np.testing.assert_array_equal(
        want, np.asarray(engine.logical(spec, t2.state)))
    np.testing.assert_array_equal(np.asarray(ref.state.version),
                                  np.asarray(t2.state.version))


def test_executor_watchdog_deprioritizes_delayed_stream():
    """An injected delay makes stream 1 a straggler; the watchdog flags it
    and the executor skips its next issue slot (work still completes)."""
    from repro import atomics
    from repro.runtime import (Executor, Fault, FaultInjector, LocalTarget,
                               StragglerWatchdog)

    n, k, width = 24, 2, 8
    target = LocalTarget(atomics.AtomicSpec(n, k, "seqlock", p_max=64))
    streams = _synth_streams(3, n=n, k=k, width=width, n_batches=8)
    ex = Executor(
        target, streams, slots=1, oversubscription=4,
        watchdog=StragglerWatchdog(n_hosts=3, threshold=1.5, patience=2),
        injector=FaultInjector([Fault(round=1, kind="delay", stream=1,
                                      seconds=0.05, rounds=4)]))
    rep = ex.run()
    assert rep["deprioritized"] > 0
    assert all(s.done() for s in streams)
    assert rep["faults_fired"] and rep["faults_fired"][0]["kind"] == "delay"


class _TickClock:
    """Deterministic stand-in for perf_counter: every call advances 1ms, so
    each issue measures exactly one tick and injected delays dominate."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


def test_straggler_flagged_after_exactly_patience_rounds():
    """The executor feeds the watchdog from the obs Recorder's per-stream
    issue latencies (`Recorder.latency_vector`): a stream degraded from
    round 1 is flagged at EXACTLY round `patience` — the first round its
    latency window is full — and the flag lands in `recorder.flags`.
    The Recorder's injectable clock makes the latencies exact (healthy
    streams 1ms, the faulted stream +50ms), so the round is deterministic."""
    from repro import atomics
    from repro.obs import Recorder
    from repro.runtime import (Executor, Fault, FaultInjector, LocalTarget,
                               StragglerWatchdog)

    patience = 3
    n, k, width = 24, 2, 8
    target = LocalTarget(atomics.AtomicSpec(n, k, "seqlock", p_max=64))
    streams = _synth_streams(4, n=n, k=k, width=width, n_batches=10)
    ex = Executor(
        target, streams, slots=1, oversubscription=4,
        watchdog=StragglerWatchdog(n_hosts=4, threshold=1.5,
                                   patience=patience),
        injector=FaultInjector([Fault(round=1, kind="delay", stream=2,
                                      seconds=0.05, rounds=10)]),
        recorder=Recorder(clock=_TickClock()))
    ex.run()
    assert ex.recorder.flags, "degraded stream never flagged"
    first_round, flagged = ex.recorder.flags[0]
    assert flagged == [2]
    assert first_round == patience
    assert ex.recorder.metrics()["exec.straggler_flags"] >= 1


def test_mcas_stream_yields_between_rounds():
    """An MCAS batch advances one protocol round per scheduling slot,
    interleaving with a foreign ops stream on DISJOINT cells: the txns
    still all commit and the ops stream's history still replays."""
    from repro import atomics
    from repro.core import engine
    from repro.runtime import Executor, LocalTarget, McasStream
    sys.path.insert(0, os.path.dirname(__file__))
    from oracle import replay_executor_history

    n, k, width, t, w = 32, 2, 8, 4, 2
    spec = atomics.AtomicSpec(n, k, "seqlock", p_max=64)
    rng = np.random.default_rng(2)
    init = rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32)
    target = LocalTarget(spec, init)
    # txns on cells [0, 16), ops stream on [16, 32): disjoint footprints
    slots = rng.permutation(16)[: t * w].reshape(t, w).astype(np.int32)
    desired = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    txns = atomics.make_txns(slots, init[slots], desired, k=k)
    from repro.runtime import SyntheticStream
    ops_stream = SyntheticStream("ops", seed=9, n=n, k=k, width=width,
                                 n_batches=4, slot_lo=16, slot_hi=32)
    mc = McasStream("mcas", txns)
    ex = Executor(target, [ops_stream, mc], slots=1, oversubscription=2)
    ex.run()
    res = mc.result()
    assert np.asarray(res.success).all()
    got = np.asarray(engine.logical(spec, target.state))
    np.testing.assert_array_equal(got[slots.ravel()],
                                  desired.reshape(-1, k))
    oracle = replay_executor_history(n, k, [width], ex.history, initial=init)
    np.testing.assert_array_equal(oracle.data[16:], got[16:])


# ---------------------------------------------------------------------------
# elastic reshard (subprocess: 8 placeholder devices)
# ---------------------------------------------------------------------------

ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    from repro.runtime import elastic_mesh, reshard_state
    from repro.launch.steps import init_train_state, make_train_step
    from repro.optim import AdamWConfig
    from repro import dist

    cfg = get_config("deepseek_7b", reduced=True)
    opt_cfg = AdamWConfig(warmup=1, total_steps=4)
    params, opt = init_train_state(cfg, opt_cfg, 0)

    # save on a 4-device mesh
    mesh4 = elastic_mesh(4, model_parallel=2, global_batch=2)
    p4, o4 = reshard_state((params, opt), cfg, mesh4)
    save_checkpoint("{d}", 1, (p4, o4), meta={{"next_step": 1}})

    # restore + reshard onto an 8-device mesh, run one step
    mesh8 = elastic_mesh(8, model_parallel=4, global_batch=2)
    (p8, o8), _ = restore_checkpoint("{d}", 1, (params, opt))
    p8, o8 = reshard_state((p8, o8), cfg, mesh8)
    rules = dist.make_rules(cfg, mesh8)
    from repro.configs.shapes import SHAPES, reduced_shape
    from repro.data import synthetic_batch
    batch = synthetic_batch(cfg, reduced_shape(SHAPES["train_4k"]),
                            seed=0, step=0)
    with dist.axis_rules(mesh8, rules):
        import jax.numpy as jnp
        step = jax.jit(make_train_step(cfg, opt_cfg))
        p2, o2, m = step(p8, o8, jax.device_put(
            batch, dist.batch_shardings(batch, mesh8, rules)))
    assert np.isfinite(float(m["loss"]))
    # leaves on mesh8 really are distributed over 8 devices
    lead = jax.tree.leaves(p2)[1]
    assert len(lead.sharding.device_set) in (2, 4, 8), lead.sharding
    print("ELASTIC_OK", float(m["loss"]))
""")


def test_elastic_reshard_4_to_8_devices(tmp_path):
    script = ELASTIC_SCRIPT.format(d=str(tmp_path))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------

def test_pipeline_pure_function_of_step():
    cfg = get_config("deepseek_7b", reduced=True)
    shape = reduced_shape(SHAPES["train_4k"])
    p = DataPipeline(cfg, shape, seed=3)
    b1 = p.batch(5)
    b2 = DataPipeline(cfg, shape, seed=3).batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p.batch(6)["tokens"], b1["tokens"])


def test_pipeline_host_sharding_assembles_global_batch():
    """4-host shards concatenate to exactly the 1-host global batch, so an
    elastic rescale does not perturb the data stream."""
    cfg = get_config("deepseek_7b", reduced=True)
    shape = reduced_shape(SHAPES["train_4k"])._replace(global_batch=4) \
        if hasattr(reduced_shape(SHAPES["train_4k"]), "_replace") else None
    from repro.configs.shapes import Shape
    shape = Shape("train_4k", 64, 4, "train")
    whole = synthetic_batch(cfg, shape, seed=1, step=2)["tokens"]
    parts = [synthetic_batch(cfg, shape, seed=1, step=2, host_id=h,
                             n_hosts=4)["tokens"] for h in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), whole)


def test_memmap_source(tmp_path):
    from repro.data import make_memmap_corpus
    cfg = get_config("deepseek_7b", reduced=True)
    from repro.configs.shapes import Shape
    shape = Shape("train_4k", 32, 2, "train")
    path = make_memmap_corpus(str(tmp_path / "corpus.bin"), 32 * 64,
                              cfg.vocab)
    p = DataPipeline(cfg, shape, seed=0, source="memmap", memmap_path=path)
    b = p.batch(0)
    assert b["tokens"].shape == (2, 32)
    assert (b["tokens"] < cfg.vocab).all()
    np.testing.assert_array_equal(
        b["tokens"],
        DataPipeline(cfg, shape, seed=0, source="memmap",
                     memmap_path=path).batch(0)["tokens"])
