"""Mesh-sharded big atomics v2: the route -> apply -> return collective round
must match the SHARED sequential oracle (tests/oracle.py) replaying the
claimed linearization order, over the registered lock-free strategy matrix,
shard counts {2, 4, 8}, the full mixed op schema (incl. cross-batch LL/SC
ABA and lapped-linker adversaries through the routing layer), the sharded
CacheHash, the all_to_all capacity-overflow contract, and a test-registered
plug-in strategy that never touches core/distributed.py.

Scenarios run in subprocesses (tests/dist_checks.py) with 8 fake host
devices via XLA_FLAGS; the shim/deprecation surface is covered in-process
by tests/test_deprecations.py.
"""

import os
import subprocess
import sys

import pytest

ALL_LOCKFREE = ["seqlock", "indirect", "cached_wf", "cached_me"]
# Under the CI BIGATOMIC_STRATEGY matrix each job runs only its own
# strategy (the other three run in sibling jobs); unset -> the full matrix.
_ENV = os.environ.get("BIGATOMIC_STRATEGY")
LOCKFREE = [_ENV] if _ENV in ALL_LOCKFREE else ALL_LOCKFREE

_HERE = os.path.dirname(os.path.abspath(__file__))
_DRIVER = os.path.join(_HERE, "dist_checks.py")


def _run(scenario: str, strategy: str | None = None, timeout: int = 900):
    cmd = [sys.executable, _DRIVER, scenario] + \
        ([strategy] if strategy else [])
    env = dict(os.environ,
               PYTHONPATH=os.path.join(_HERE, "..", "src"))
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert f"DIST_OK:{scenario}" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


@pytest.mark.parametrize("strategy", LOCKFREE)
def test_mixed_kind_batches_match_oracle_sharded(strategy):
    """Random mixed LOAD/STORE/CAS/LL/SC/VALIDATE batches, shards {2,4,8}."""
    _run("mixed", strategy)


def test_routing_levers_preserve_semantics():
    """dedup_loads × interleave × route_capacity all replay against the
    shared oracle (semantics never change, only wire cost)."""
    _run("levers")


def test_llsc_adversaries_through_routing():
    """Cross-batch ABA (remote byte restore) + lapped linker, sharded."""
    _run("sync_adversary")


def test_all_to_all_overflow_contract():
    """Capacity-rejected lanes: reported in the overflow mask with
    success=False, never silently dropped, never corrupting any shard."""
    _run("overflow")


def test_plugin_strategy_runs_sharded():
    """A strategy registered from the test process runs sharded without
    editing core/distributed.py (ISSUE 3 acceptance)."""
    _run("plugin")


def test_sharded_cachehash_matches_oracle():
    """Key-owner-routed FIND/INSERT/DELETE vs the dict-model oracle,
    shards {2,4,8}, plus the hot-key capacity contract."""
    _run("hash")


def test_serving_engine_on_sharded_table():
    """Sharded page table + sharded admission/slot rings: token-identical
    to the single-device engine, still one dispatch per decode step."""
    _run("serving")


@pytest.mark.parametrize("strategy", LOCKFREE)
def test_cross_shard_mcas_matches_txn_oracle(strategy):
    """Two-round prepare/commit MCAS vs the whole-transaction oracle,
    shards {2,4,8}, incl. an all-shards-spanning abort/commit pair."""
    _run("mcas", strategy)


def test_transactional_map_sharded():
    """Read/write sets spanning shards commit serializably; the counter
    conflict storm serializes one commit per round."""
    _run("txnmap")


def test_txn_plugin_strategy_runs_sharded():
    """A test-registered strategy runs cross-shard MCAS + the sharded map
    without touching core (ISSUE 4 acceptance)."""
    _run("txn_plugin")


def test_two_level_routing_matches_oracle():
    """Hierarchical intra-node combine + one cross-node all_to_all replays
    against the shared oracle (interleave × capacity variants)."""
    _run("twolevel")


@pytest.mark.parametrize("strategy", [s for s in ("seqlock", "cached_wf")
                                      if s in LOCKFREE])
def test_oversubscribed_executor_recovers_from_shard_loss(strategy):
    """Streams {2,4,8} × injected mid-round shard loss: checkpoint-restore,
    reshard onto survivors, journal replay — the whole interleaving
    (across the recovery boundary) replays through one sequential oracle
    (ISSUE 7 acceptance)."""
    _run("executor", strategy)


@pytest.mark.parametrize("mode", ["pallas", "xla"])
def test_sharded_round_runs_the_engines_kernels(mode):
    """Each shard runs `engine.round_for` (Pallas kernels interpreted, or
    the XLA tiers) on a YCSB-A-like Zipf stream: equal to the oracle and
    the benchmark's reference; the per-device build, the sharded gather
    and the blocked untouched count agree with their whole-table twins;
    a traced call holds the `dist.*` scopes and spans."""
    _run("round", mode)


def test_elastic_reshard_round_trips():
    """Table 8->6->4->8 preserving values+versions (LL link survives);
    training state through the same chain bit-identically, with dropped
    devices reported by mesh_plan."""
    _run("elastic")
