"""repro.guard acceptance suite (ISSUE 10).

The contracts, all tier-1:

  * DETECTION IS TOTAL — every boundary-injected bit flip / torn write is
    detected by the scrub pass (digest chain bijectivity + structural
    invariants), 100% across strategies, fields, words and bits.
  * CHAOS IS SURVIVABLE — >= 50 seeded schedules x 4 strategies of mixed
    scheduling + data-plane faults: every injection is repaired or
    quarantined, the oracle replay of the surviving history bit-agrees on
    every delivered result and every non-quarantined cell, and zero
    corruptions go undetected.
  * DEGRADATION IS GRACEFUL — streams whose cells are all quarantined
    retry through a backoff budget and shed with a recorded reason while
    the rest of the run completes; serving submit() sheds with a typed
    verdict under sustained overload.
  * CHECKPOINTS SELF-VERIFY — per-leaf CRCs round-trip every dtype
    (bf16/uint32 included), and restore falls back to the newest
    VERIFYING step past corrupt or truncated damage.
  * OFF IS FREE — BIGATOMIC_GUARD unset/off builds no scrubber and adds
    ZERO new traces to the engine round across an executor run.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from oracle import replay_executor_history
from repro import guard
from repro.analysis import tracing
from repro.core import engine
from repro.core.specs import AtomicSpec, VersionSpec
from repro.guard.chaos import CHAOS_STRATEGIES, run_chaos, verify_chaos
from repro.guard.inject import inject_table_fault
from repro.guard.scrub import ScrubReport, Scrubber, digest_np
from repro.guard.scrub import scrub as scrub_pass
from repro.guard.scrub import _cell_digest
from repro.runtime.executor import Executor, LocalTarget
from repro.runtime.faults import DATA_KINDS, Fault, FaultInjector, IssueFault
from repro.runtime.streams import SyntheticStream
from repro.sync.queue import BackoffPolicy

STRATEGIES = CHAOS_STRATEGIES
CHAOS_SEEDS = int(os.environ.get("BIGATOMIC_CHAOS_SEEDS", "50"))


def _random_state(spec, seed):
    rng = np.random.default_rng(seed)
    init = rng.integers(0, 2 ** 32, (spec.n, spec.k), dtype=np.uint32)
    return engine.init(spec, init)


# ---------------------------------------------------------------------------
# Detection is total.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_bit_flip_and_torn_write_detection_is_100pct(strategy):
    """Sweep random single-cell corruptions: EVERY one lands in the scrub
    report's detected set — the digest chain makes this structural."""
    spec = AtomicSpec(32, 3, strategy, 16)
    for seed in range(30):
        state = _random_state(spec, seed)
        baseline = np.asarray(guard.cell_digest(spec, state))
        rng = np.random.default_rng(1000 + seed)
        kind = "bit_flip" if seed % 2 else "torn_write"
        fault = Fault(round=1, kind=kind)
        corrupt, info = inject_table_fault(spec, state, fault, rng)
        report = scrub_pass(spec, corrupt, baseline=baseline)
        assert info["slot"] in report.detected, (strategy, seed, info)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_clean_state_scrubs_clean(strategy):
    spec = AtomicSpec(32, 3, strategy, 16)
    state = _random_state(spec, 7)
    baseline = np.asarray(guard.cell_digest(spec, state))
    report = scrub_pass(spec, state, baseline=baseline)
    assert report.clean and not report.invariant_violations
    assert guard.violation_mask(spec, state).sum() == 0


def test_invariants_name_the_right_violation():
    """Field-targeted corruption trips the per-strategy invariant the
    design names for it (DESIGN.md §11 table)."""
    rng = np.random.default_rng(0)

    def viols(strategy, **kw):
        spec = AtomicSpec(16, 2, strategy, 8)
        state = _random_state(spec, 3)
        corrupt, _ = inject_table_fault(
            spec, state, Fault(round=1, kind="bit_flip", slot=5, **kw), rng)
        return {name: np.flatnonzero(np.asarray(m)).tolist()
                for name, m in guard.check_invariants(spec, corrupt).items()
                if np.asarray(m).any()}

    # odd version at rest = writer died mid-cell
    assert viols("seqlock", field="version", bit=0) == \
        {"version_parity": [5]}
    # indirect: a flipped high bptr bit leaves [0, pool); shadow disagrees
    v = viols("indirect", field="bptr", bit=20)
    assert "pointer_range" in v and v["pointer_range"] == [5]
    # indirect: a pool flip on the live node breaks the commit shadow
    assert viols("indirect", field="pool", word=0) == \
        {"shadow_agrees": [5]}
    # cached_wf: backup flip breaks cache/backup agreement
    assert viols("cached_wf", field="pool", word=0) == \
        {"cache_matches_backup": [5]}
    # cached_me: bptr damage breaks the tagged-null encoding
    assert viols("cached_me", field="bptr", bit=3) == {"tagged_null": [5]}


def test_version_list_invariants():
    import repro.txn.versionlist as vl
    vspec = VersionSpec(8, 2, 4, "seqlock", 8)
    vstate = vl.init(vspec)
    slots = jnp.arange(8, dtype=jnp.int32)
    for ts in range(1, 6):
        vstate = vl.publish(vspec, vstate, slots,
                            jnp.full((8, 2), ts, jnp.uint32),
                            jnp.full((8,), ts, jnp.uint32))
    masks = {k: np.asarray(v) for k, v in
             guard.check_version_list(vspec, vstate).items()}
    assert all(m.sum() == 0 for m in masks.values()), masks
    # corrupt slot 3's head prev word: the ring no longer agrees
    data = np.array(vstate.table.data)
    data[3, vspec.k + 1] ^= 1
    bad = vstate._replace(table=vstate.table._replace(
        data=jnp.asarray(data)))
    got = {k: np.flatnonzero(np.asarray(v)).tolist() for k, v in
           guard.check_version_list(vspec, bad).items()}
    assert got["head_prev_agrees"] == [3]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_pallas_digest_equals_xla(strategy):
    """The blocked Pallas digest pass computes the XLA twin bit-exactly
    (interpret mode on CPU, per kernels/engine_round resolution)."""
    spec = AtomicSpec(20, 3, strategy, 8)   # 20 forces ragged-tail padding
    state = _random_state(spec, 11)
    a = np.asarray(_cell_digest(spec, state, "xla", True))
    b = np.asarray(_cell_digest(spec, state, "pallas", True))
    np.testing.assert_array_equal(a, b)


def test_numpy_digest_matches_jitted():
    spec = AtomicSpec(16, 2, "seqlock", 8)
    state = _random_state(spec, 5)
    a = np.asarray(guard.cell_digest(spec, state))
    b = digest_np(np.asarray(engine.logical(spec, state)),
                  np.asarray(state.version))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Fault injector: determinism + ordering contract.
# ---------------------------------------------------------------------------

def test_injector_determinism_same_seed_same_draws():
    """Two runs of one schedule under one seed realize IDENTICAL victim
    choices and final table bits (the documented per-fault rng contract)."""
    outs = []
    for _ in range(2):
        res = run_chaos(21, "indirect", data_faults=4)
        ex = res["executor"]
        outs.append((
            [info for _r, _f, info in ex.data_faults],
            np.asarray(engine.logical(res["spec"], ex.target.state)),
            ex.scrubber.poison.copy()))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    np.testing.assert_array_equal(outs[0][2], outs[1][2])


def test_injector_seed_changes_draws():
    a = FaultInjector([Fault(round=1, kind="bit_flip")], seed=1)
    b = FaultInjector([Fault(round=1, kind="bit_flip")], seed=2)
    assert a.rng(0).integers(2 ** 31) != b.rng(0).integers(2 ** 31)


def test_injector_ordering_contract():
    """Scheduling faults honor (round, after_issues); data faults defer
    to the boundary poll regardless of after_issues; both fire once."""
    faults = [Fault(round=2, kind="bit_flip"),
              Fault(round=1, kind="delay", stream=0, after_issues=2),
              Fault(round=1, kind="torn_write")]
    inj = FaultInjector(faults, seed=0)
    assert inj.poll(1, 0) == []                      # before after_issues
    assert [f.kind for f in inj.poll(1, 2)] == ["delay"]
    # boundary of round 1: only the round-1 data fault, original order
    due = inj.poll_boundary(1)
    assert [f.kind for f, _rng in due] == ["torn_write"]
    due = inj.poll_boundary(2)
    assert [f.kind for f, _rng in due] == ["bit_flip"]
    assert inj.exhausted and len(inj.fired) == 3


# ---------------------------------------------------------------------------
# Chaos: zero undetected corruptions.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_chaos_zero_undetected_corruptions(strategy):
    """>= 50 seeded schedules per strategy: every injected fault detected
    AND repaired-or-quarantined, oracle replay bit-agrees on every
    delivered result and every non-quarantined cell."""
    injected = 0
    for seed in range(CHAOS_SEEDS):
        res = run_chaos(seed, strategy,
                        data_faults=2 + seed % 3,
                        sched_faults=seed % 2,
                        n_batches=3 + seed % 2, width=5)
        verdict = verify_chaos(res)
        assert verdict["ok"], (strategy, seed, verdict)
        injected += verdict["injected_data_faults"]
    assert injected >= CHAOS_SEEDS          # schedules actually bit


def test_chaos_with_checkpoint_damage(tmp_path):
    """ckpt_corrupt / ckpt_truncate in the schedule: the run survives and
    restore_latest still finds a verifying step afterwards."""
    from repro.checkpoint import disk
    res = run_chaos(5, "seqlock", ckpt_faults=2, data_faults=1,
                    checkpoint_dir=str(tmp_path))
    assert verify_chaos(res)["ok"]
    damaged = [info for _r, f, info in res["executor"].data_faults
               if f.kind in ("ckpt_corrupt", "ckpt_truncate")]
    assert damaged, "schedule should have hit a checkpoint leaf"
    template = res["executor"]._ck_payload()
    _state, meta, step = disk.restore_latest(str(tmp_path), template)
    assert not disk.verify_checkpoint(str(tmp_path), damaged[0]["step"]) \
        or step >= damaged[0]["step"]


# ---------------------------------------------------------------------------
# Graceful degradation: poison contract, retry budget, stream shedding.
# ---------------------------------------------------------------------------

def test_poisoned_cells_fail_ops_and_streams_shed(monkeypatch):
    """Quarantine the slot range four confined streams hammer: their ops
    come back success=False (lanes IDLE-rewritten, oracle agrees), they
    burn their retry budgets, shed with a recorded reason — and a fifth
    healthy stream still completes."""
    monkeypatch.setenv("BIGATOMIC_GUARD", "on")
    n, k, width = 16, 2, 4
    spec = AtomicSpec(n, k, "seqlock", 16)
    victims = [SyntheticStream(f"s{i}", seed=500 + i, n=n, k=k, width=width,
                               n_batches=8, slot_lo=0, slot_hi=4)
               for i in range(4)]
    healthy = SyntheticStream("healthy", seed=555, n=n, k=k, width=width,
                              n_batches=8, slot_lo=4)
    faults = [Fault(round=2, kind="bit_flip", slot=s, field="data")
              for s in range(4)]
    ex = Executor(LocalTarget(spec), victims + [healthy],
                  injector=FaultInjector(faults, seed=3),
                  checkpoint_every=0,   # only the round-0 baseline: every
                  retry_budget=1,       # written cell stays dirty =>
                  backoff=BackoffPolicy("none"))             # quarantine
    rep = ex.run()

    assert rep["poisoned"] == 4
    assert sorted(s["stream"] for s in rep["shed"]) == [0, 1, 2, 3]
    assert rep["shed"][0]["reason"] == "all lanes target quarantined cells"
    assert healthy.done() and not victims[0].done()
    # the poison contract, end to end: post-quarantine victim batches
    # delivered all-False success over fully-IDLE journaled ops
    quarantine_round = min(r.round for r in ex.scrubber.reports
                           if r.quarantined)
    assert quarantine_round >= 2
    post = [r for r in ex.history if r.stream == 0
            and np.asarray(r.ops.kind == engine.IDLE).all()]
    assert post, "expected fully-masked victim batches after quarantine"
    assert all(not r.success.any() for r in post)
    # the surviving history replays bit-exactly through the oracle
    replay_executor_history(n, k, [width] * 5, ex.history, check=True)
    assert rep["events"]["exec.shed"] == 4


def test_issue_exception_retries_then_sheds(monkeypatch):
    """A target whose issue keeps raising: the stream rolls back, backs
    off, and sheds after the budget instead of crashing the run."""
    monkeypatch.delenv("BIGATOMIC_GUARD", raising=False)
    spec = AtomicSpec(8, 2, "seqlock", 8)
    target = LocalTarget(spec)
    boom = {"left": 100}

    real_issue = target.issue

    def flaky_issue(ops, ctx, *, donate=True):
        if boom["left"] > 0:
            boom["left"] -= 1
            raise IssueFault("injected issue failure")
        return real_issue(ops, ctx, donate=donate)

    target.issue = flaky_issue
    s = SyntheticStream("s0", seed=1, n=8, k=2, width=4, n_batches=3)
    ex = Executor(target, [s], retry_budget=2,
                  backoff=BackoffPolicy("none"))
    rep = ex.run()
    assert rep["shed"] and rep["shed"][0]["reason"] == "issue raised"
    assert rep["shed"][0]["attempts"] == 3 and not s.done()


def test_serving_overload_sheds_typed(monkeypatch):
    """submit() under sustained saturation returns a typed Shed verdict;
    without a policy the legacy full-ring RuntimeError is preserved."""
    import jax

    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.serving import (Admitted, OverloadPolicy, Request,
                               ServingEngine, Shed)

    cfg = dataclasses.replace(get_config("deepseek_7b", reduced=True),
                              param_dtype="float32",
                              compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def req(rid):
        return Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab, 4).astype(np.int32), max_new_tokens=64)

    eng = ServingEngine(cfg, params, max_batch=2, n_pages=32, page_size=8,
                        max_queue=4,
                        overload=OverloadPolicy(watermark=0.5, patience=1))
    assert isinstance(eng.submit(req(0)), Admitted)
    assert isinstance(eng.submit(req(1)), Admitted)
    eng.step()                      # both prefill: no free decode slot
    verdicts = [eng.submit(req(2 + i)) for i in range(6)]
    sheds = [v for v in verdicts if isinstance(v, Shed)]
    assert sheds, verdicts
    assert sheds[0].reason in ("sustained overload",
                               "admission queue full")
    assert sheds[0].free_slots == 0 and sheds[0].queue_depth >= 2
    assert eng.shed_count == len(sheds)
    # a shed rid is NOT parked in the registry
    assert all(v.rid not in eng.requests for v in sheds)

    legacy = ServingEngine(cfg, params, max_batch=2, n_pages=32,
                           page_size=8, max_queue=2)
    for rid in range(legacy.admit_q.capacity):
        legacy.submit(req(rid))
    with pytest.raises(RuntimeError, match="admission queue full"):
        legacy.submit(req(99))


# ---------------------------------------------------------------------------
# Checkpoint hardening.
# ---------------------------------------------------------------------------

def test_checkpoint_crc_roundtrip_all_dtypes(tmp_path):
    import ml_dtypes

    from repro.checkpoint import disk
    state = {
        "f32": np.linspace(0, 1, 12, dtype=np.float32).reshape(3, 4),
        "u32": np.arange(8, dtype=np.uint32),
        "bf16": np.arange(6, dtype=np.float32).astype(ml_dtypes.bfloat16),
        "b": np.array([True, False]),
    }
    disk.save_checkpoint(str(tmp_path), 3, state)
    assert disk.verify_checkpoint(str(tmp_path), 3)
    back, _meta = disk.restore_checkpoint(str(tmp_path), 3, state,
                                          verify=True)
    for key, want in state.items():
        got = np.asarray(back[key])
        assert got.dtype == want.dtype, key
        np.testing.assert_array_equal(
            got.view(np.uint8), np.asarray(want).view(np.uint8), err_msg=key)


def test_restore_latest_falls_back_past_damage(tmp_path):
    from repro.checkpoint import disk
    state = {"x": np.arange(16, dtype=np.uint32)}
    disk.save_checkpoint(str(tmp_path), 1, state)
    good = {"x": np.arange(16, dtype=np.uint32) + 100}
    disk.save_checkpoint(str(tmp_path), 2, good)
    bad = {"x": np.arange(16, dtype=np.uint32) + 200}
    disk.save_checkpoint(str(tmp_path), 3, bad)

    # corrupt step 3 (flip one payload byte), truncate step 2's leaf
    leaf3 = tmp_path / "step_00000003" / "x.npy"
    raw = bytearray(leaf3.read_bytes())
    raw[-1] ^= 0xFF
    leaf3.write_bytes(bytes(raw))
    leaf2 = tmp_path / "step_00000002" / "x.npy"
    leaf2.write_bytes(leaf2.read_bytes()[: leaf2.stat().st_size // 2])

    assert not disk.verify_checkpoint(str(tmp_path), 3)
    assert not disk.verify_checkpoint(str(tmp_path), 2)
    assert disk.verify_checkpoint(str(tmp_path), 1)
    restored, _meta, step = disk.restore_latest(str(tmp_path), state)
    assert step == 1
    np.testing.assert_array_equal(restored["x"], state["x"])
    with pytest.raises(disk.CheckpointError):
        disk.restore_checkpoint(str(tmp_path), 3, state, verify=True)


def test_restore_latest_no_verifying_step(tmp_path):
    from repro.checkpoint import disk
    state = {"x": np.arange(4, dtype=np.uint32)}
    with pytest.raises(FileNotFoundError):
        disk.restore_latest(str(tmp_path), state)
    disk.save_checkpoint(str(tmp_path), 1, state)
    leaf = tmp_path / "step_00000001" / "x.npy"
    leaf.write_bytes(b"")
    with pytest.raises(disk.CheckpointError):
        disk.restore_latest(str(tmp_path), state)


def test_executor_resume_skips_damaged_newest(monkeypatch, tmp_path):
    """End to end: damage the newest disk checkpoint after a run; a fresh
    executor resumes from the older VERIFYING step and finishes with the
    table bit-identical to the uninterrupted run."""
    monkeypatch.delenv("BIGATOMIC_GUARD", raising=False)
    n, k, width = 16, 2, 4

    def mk(ckdir=None):
        spec = AtomicSpec(n, k, "seqlock", 16)
        streams = [SyntheticStream("s0", seed=77, n=n, k=k, width=width,
                                   n_batches=6)]
        return Executor(LocalTarget(spec), streams, checkpoint_dir=ckdir,
                        checkpoint_every=2)

    ex1 = mk(str(tmp_path))
    ex1.run()
    want = ex1.target.snapshot()

    from repro.checkpoint import disk
    steps = disk.list_steps(str(tmp_path))
    assert len(steps) >= 2
    newest = tmp_path / f"step_{steps[-1]:08d}"
    victim = sorted(newest.glob("*.npy"))[0]
    victim.write_bytes(victim.read_bytes()[:8])
    assert not disk.verify_checkpoint(str(tmp_path), steps[-1])

    ex2 = mk()                          # no ckpt dir: don't re-save steps
    resumed_round = ex2.resume(str(tmp_path))
    assert resumed_round == steps[-2]
    ex2.run()
    got = ex2.target.snapshot()
    np.testing.assert_array_equal(got["logical"], want["logical"])
    np.testing.assert_array_equal(got["versions"], want["versions"])


# ---------------------------------------------------------------------------
# Off is free.
# ---------------------------------------------------------------------------

def _run_once(seed):
    spec = AtomicSpec(16, 2, "cached_me", 16)
    streams = [SyntheticStream(f"s{i}", seed=seed + i, n=16, k=2, width=4,
                               n_batches=3) for i in range(2)]
    ex = Executor(LocalTarget(spec), streams)
    rep = ex.run()
    return ex, rep


def test_guard_off_is_free(monkeypatch):
    """BIGATOMIC_GUARD unset: no scrubber exists, no scrub/shed state is
    recorded, and a full executor run adds ZERO new traces to the engine
    round — the issue path is byte-identical to the unguarded build."""
    monkeypatch.delenv("BIGATOMIC_GUARD", raising=False)
    ex, _rep = _run_once(800)                 # warm every signature
    assert ex.scrubber is None
    with tracing.assert_max_new_traces(engine._apply, 0):
        ex, rep = _run_once(900)
    assert ex.scrubber is None
    assert rep["scrubs"] == [] and rep["poisoned"] == 0
    assert "exec.scrubs" not in rep["events"]


def test_guard_env_validation(monkeypatch):
    monkeypatch.setenv("BIGATOMIC_GUARD", "sideways")
    with pytest.raises(ValueError, match="BIGATOMIC_GUARD"):
        guard.configured()
    monkeypatch.setenv("BIGATOMIC_GUARD", "on")
    assert guard.enabled()


# ---------------------------------------------------------------------------
# Satellites: compare.py suite handling, scrub report JSON.
# ---------------------------------------------------------------------------

def test_compare_missing_suite_warns_not_fails(capsys):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks import compare as cmp

    old = {"schema": 1, "suites": {
        "atomics": [{"name": "atomics/x", "ops_s": 100.0}],
        "faults": [{"name": "faults/scrub/seqlock", "ops_s": 50.0}]}}
    new_missing_suite = {"schema": 1, "suites": {
        "atomics": [{"name": "atomics/x", "ops_s": 101.0}]}}
    rows = list(cmp.compare(old, new_missing_suite, 0.10))
    verdicts = {name: v for name, _m, _o, _n, _d, v in rows}
    assert verdicts["faults/scrub/seqlock"] == "MISSING-SUITE"
    # a row missing WITHIN a surviving suite is still a hard regression
    new_missing_row = {"schema": 1, "suites": {
        "atomics": [], "faults": old["suites"]["faults"]}}
    rows = list(cmp.compare(old, new_missing_row, 0.10))
    assert ("atomics/x", "-", None, None, None, "MISSING") in rows


def test_scrub_report_round_trips_json():
    res = run_chaos(2, "seqlock")
    import json
    for rep in res["executor"].scrubber.reports:
        doc = json.loads(json.dumps(rep.to_json()))
        assert doc["clean"] == rep.clean
        assert doc["n"] == rep.n and doc["strategy"] == "seqlock"
