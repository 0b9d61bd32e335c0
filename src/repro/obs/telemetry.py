"""Tier-1 observability: in-graph engine counters (DESIGN.md §10).

The paper's headline claims are RATES — fast-path hit frequency, slow-path
round counts, CAS retry behavior under contention — and the engine already
materializes every signal they need (`fast_path_ok`, `ApplyStats`, per-lane
`success`, overflow masks).  This module accumulates those signals into a
`Telemetry` pure-pytree of int32 counters INSIDE the existing jitted
programs: the counter state rides the jit boundary as one extra (tiny)
pytree argument and output, so counting adds no extra host->device
dispatches and no extra HBM traffic beyond the scalar counters themselves.

The gate is the static BIGATOMIC_OBS flag:

  off       (default) the counter pytree is None everywhere — entry points
            trace the EXACT pre-observability programs (asserted via
            `analysis/tracing.assert_max_new_traces`): zero cost when off.
  counters  the global `Telemetry` threads through `engine.apply`,
            `txn.mcas`, `distributed.apply` (one extra scalar-accumulate
            dispatch per collective round), and host-side retry loops
            (`sync.queue`, `serving.engine`) record into a host counter
            dict.

Timelines are not this flag's business: the entry points and the executor
write host spans and device scopes into the JAX profiler's trace
(`jax.profiler.trace`), on one clock with the device's ops.

Like BIGATOMIC_ENGINE_KERNEL, the flag is read per call and threaded as a
static jit argument (or None-vs-pytree structure), so flipping it
mid-process retraces instead of silently reusing the other mode's program.

Counters are int32 (jax x64 is disabled repo-wide): they wrap at 2^31.
Call `reset()` per measurement window; a window of >2e9 of any single
event is out of scope for these counters.

Every counter is recomputable bit-exactly from the claimed linearization
orders — `tests/oracle.py::TelemetryOracle` is the numpy recount, and
tests/test_obs.py holds the equivalence to it across strategies and
engine-kernel modes.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

N_KINDS = 10          # engine.LOAD .. engine.DELETE
N_HIST = 16           # log2 contention buckets: [1], [2,3], [4,7], ...

_MODES = ("off", "counters")

_KIND_NAMES = ("load", "store", "cas", "idle", "ll", "sc", "validate",
               "find", "insert", "delete")


def configured_mode() -> str:
    """The observability mode requested by the environment (read per call,
    exactly like `kernels.engine_round.configured_mode`, so a mid-process
    flip always retraces)."""
    mode = os.environ.get("BIGATOMIC_OBS", "off")
    if mode not in _MODES:
        raise ValueError(f"BIGATOMIC_OBS={mode!r}; expected one of {_MODES}")
    return mode


def counters_on() -> bool:
    return configured_mode() != "off"


class Telemetry(NamedTuple):
    """The in-graph counter state: a pure pytree of int32 scalars (plus the
    per-kind vector and the contention histogram).  All fields accumulate;
    `snapshot()` names them (DESIGN.md §10 metric table).

    Engine counters (per `engine.apply` batch):
      batches         table batches observed
      ops_kind        [N_KINDS] lanes per op kind (IDLE padding included)
      fast_eligible   batches passing `fast_path_ok` (provably independent)
      fast_taken      batches whose round resolved on the fused fast path
                      (the branch the `lax.cond` in `make_round` took;
                      always 0 under BIGATOMIC_ENGINE_KERNEL=off)
      rounds          sum of ApplyStats.rounds (serialization rounds L)
      slow_rounds     rounds spent on batches NOT taken by the fast path
                      (the slow-path replay cost)
      slow_windows    window round trips of the batches NOT taken by the
                      fast path: their distinct live windows
                      (`engine_round.slow_windows`), one DMA in and one
                      out each in the slow kernel
      cas_fail        active CAS lanes that failed
      sc_fail         active SC lanes that failed (stale link or lost race)
      raced_loads     loads whose cell saw a same-batch write
      dirty_cells     distinct cells written per batch, summed
      contention_hist [N_HIST] cells by log2(active lanes targeting them):
                      bucket b counts cells with lane count in [2^b, 2^(b+1))
    Read-protocol counters:
      torn_retries    reads that observed a torn/locked cell (ok=False)
    MCAS protocol counters (per `txn.mcas` attempt round):
      mcas_commits / mcas_aborts   txns resolved either way
      mcas_rounds                  attempt rounds executed
      mcas_backoff                 arbitration losses (backoff events)
    Distributed counters (per `distributed.apply` collective round):
      route_overflow    lanes rejected by route capacity
      collective_rounds collective rounds executed
      collective_words  sum of `distributed.collective_words(dspec)`
    """

    batches: jax.Array
    ops_kind: jax.Array
    fast_eligible: jax.Array
    fast_taken: jax.Array
    rounds: jax.Array
    slow_rounds: jax.Array
    slow_windows: jax.Array
    cas_fail: jax.Array
    sc_fail: jax.Array
    raced_loads: jax.Array
    dirty_cells: jax.Array
    contention_hist: jax.Array
    torn_retries: jax.Array
    mcas_commits: jax.Array
    mcas_aborts: jax.Array
    mcas_rounds: jax.Array
    mcas_backoff: jax.Array
    route_overflow: jax.Array
    collective_rounds: jax.Array
    collective_words: jax.Array


def init_telemetry() -> Telemetry:
    z = jnp.int32(0)
    return Telemetry(
        batches=z, ops_kind=jnp.zeros((N_KINDS,), jnp.int32),
        fast_eligible=z, fast_taken=z, rounds=z, slow_rounds=z,
        slow_windows=z, cas_fail=z, sc_fail=z, raced_loads=z, dirty_cells=z,
        contention_hist=jnp.zeros((N_HIST,), jnp.int32),
        torn_retries=z, mcas_commits=z, mcas_aborts=z, mcas_rounds=z,
        mcas_backoff=z, route_overflow=z, collective_rounds=z,
        collective_words=z)


# ---------------------------------------------------------------------------
# In-graph accumulators (traced inside the existing jitted programs).
# ---------------------------------------------------------------------------

def contention_bucket(c: jax.Array) -> jax.Array:
    """floor(log2(c)) clipped to N_HIST-1, via integer threshold compares —
    bit-exact and mirrored verbatim by the numpy recount (no float log)."""
    th = jnp.left_shift(jnp.int32(1), jnp.arange(1, N_HIST, dtype=jnp.int32))
    return jnp.sum((c[:, None] >= th[None, :]).astype(jnp.int32), axis=1)


def count_table(t: Telemetry, n: int, ops, result, stats, *,
                eligible: jax.Array, taken: jax.Array,
                windows: jax.Array) -> Telemetry:
    """Accumulate one `engine.apply` batch from masks the round already
    materialized (ops, per-lane success, ApplyStats, and the fast-path
    predicate / taken branch from `engine_round.path_counts`), and the
    batch's window count `engine_round.slow_windows`."""
    kind, slot = ops.kind, ops.slot
    success = result.success
    one = jnp.int32(1)
    active = kind != 3                                    # engine.IDLE
    in_range = (slot >= 0) & (slot < n)
    elig = eligible.astype(jnp.int32)
    taken = taken.astype(jnp.int32)
    # Per-cell active-lane counts: the same scatter `fast_path_ok` builds,
    # so XLA CSEs it inside the fused round (no second pass over the batch).
    cslot = jnp.where(active & in_range, slot, n)
    counts = jnp.zeros((n + 1,), jnp.int32).at[cslot].add(1, mode="drop")
    c = counts[:n]
    hist = jnp.zeros((N_HIST,), jnp.int32).at[
        jnp.where(c > 0, contention_bucket(c), N_HIST)].add(1, mode="drop")
    return t._replace(
        batches=t.batches + one,
        ops_kind=t.ops_kind.at[kind].add(1, mode="drop"),
        fast_eligible=t.fast_eligible + elig,
        fast_taken=t.fast_taken + taken,
        rounds=t.rounds + stats.rounds,
        slow_rounds=t.slow_rounds + (1 - taken) * stats.rounds,
        slow_windows=t.slow_windows + (1 - taken) * windows,
        cas_fail=t.cas_fail + jnp.sum(
            (active & (kind == 2) & ~success).astype(jnp.int32)),
        sc_fail=t.sc_fail + jnp.sum(
            (active & (kind == 5) & ~success).astype(jnp.int32)),
        raced_loads=t.raced_loads + stats.n_raced_loads,
        dirty_cells=t.dirty_cells + stats.n_dirty_cells,
        contention_hist=t.contention_hist + hist)


def count_read(t: Telemetry, ok: jax.Array) -> Telemetry:
    """Accumulate one `engine.read` batch: ok=False lanes observed a torn/
    locked cell and must retry (blocking strategies only)."""
    return t._replace(torn_retries=t.torn_retries
                      + jnp.sum((~ok).astype(jnp.int32)))


def count_mcas_round(t: Telemetry, committed, failed_now,
                     lost) -> Telemetry:
    """Accumulate one MCAS attempt round from the protocol's own masks."""
    i32 = lambda m: jnp.sum(m.astype(jnp.int32))  # noqa: E731
    return t._replace(
        mcas_commits=t.mcas_commits + i32(committed),
        mcas_aborts=t.mcas_aborts + i32(failed_now),
        mcas_rounds=t.mcas_rounds + jnp.int32(1),
        mcas_backoff=t.mcas_backoff + i32(lost))


@jax.jit
def _dist_accum(t: Telemetry, overflow, words) -> Telemetry:
    return t._replace(
        route_overflow=t.route_overflow
        + jnp.sum(overflow.astype(jnp.int32)),
        collective_rounds=t.collective_rounds + jnp.int32(1),
        collective_words=t.collective_words + words)


# ---------------------------------------------------------------------------
# The global store: one device-side Telemetry + one host-side counter dict.
# ---------------------------------------------------------------------------

_telem: Telemetry | None = None
_host: dict[str, int] = {}


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def telemetry() -> Telemetry:
    """The live global counter pytree (device arrays; initialized lazily)."""
    global _telem
    if _telem is None:
        _telem = init_telemetry()
    return _telem


def carry_in(*samples) -> Telemetry | None:
    """The counter pytree an entry point should thread into its jitted
    program, or None when counting is off OR the entry point is itself
    being traced (any tracer among the sample pytrees' leaves means an
    outer jit owns this call, and the global must never absorb tracers —
    the outer program's own entry point does the counting)."""
    if not counters_on():
        return None
    for s in samples:
        if any(_is_tracer(leaf) for leaf in jax.tree_util.tree_leaves(s)):
            return None
    return telemetry()


def carry_out(t: Telemetry) -> None:
    """Absorb the counter pytree an entry point got back."""
    global _telem
    _telem = t


def record(**events: int) -> None:
    """Host-side counters (queue retry loops, serving dispatch counts,
    executor events): plain ints keyed by metric name, merged into
    `snapshot()`.  No-op when counting is off."""
    if not counters_on():
        return
    for name, v in events.items():
        _host[name] = _host.get(name, 0) + int(v)


def record_dist(overflow, words: int) -> None:
    """Accumulate one distributed collective round (route-overflow mask +
    the static `collective_words(dspec)` count).  One tiny scalar-
    accumulate dispatch per round when counters are on; nothing when off
    (the `counters_on` gate lives in the caller)."""
    carry_out(_dist_accum(telemetry(), overflow, jnp.int32(words)))


def reset() -> None:
    """Zero every counter (device and host)."""
    global _telem
    _telem = None
    _host.clear()


def snapshot() -> dict:
    """Every counter as one flat {metric_name: int} dict — THE stable
    metric-name schema (DESIGN.md §10).  Pulls the device counters to host;
    host-side counters (`record`) merge in under their own names."""
    t = telemetry()
    out = {"engine.batches": int(t.batches)}
    kinds = np.asarray(t.ops_kind)
    for j, name in enumerate(_KIND_NAMES):
        out[f"engine.ops.{name}"] = int(kinds[j])
    out["engine.fast.eligible"] = int(t.fast_eligible)
    out["engine.fast.taken"] = int(t.fast_taken)
    out["engine.rounds.total"] = int(t.rounds)
    out["engine.rounds.slow"] = int(t.slow_rounds)
    out["engine.slow.windows"] = int(t.slow_windows)
    out["engine.fail.cas"] = int(t.cas_fail)
    out["engine.fail.sc"] = int(t.sc_fail)
    out["engine.loads.raced"] = int(t.raced_loads)
    out["engine.cells.dirty"] = int(t.dirty_cells)
    hist = np.asarray(t.contention_hist)
    for b in range(N_HIST):
        out[f"engine.contention.log2_{b:02d}"] = int(hist[b])
    out["read.torn_retries"] = int(t.torn_retries)
    out["mcas.commits"] = int(t.mcas_commits)
    out["mcas.aborts"] = int(t.mcas_aborts)
    out["mcas.rounds"] = int(t.mcas_rounds)
    out["mcas.backoff"] = int(t.mcas_backoff)
    out["dist.route_overflow"] = int(t.route_overflow)
    out["dist.rounds"] = int(t.collective_rounds)
    out["dist.words"] = int(t.collective_words)
    out.update(_host)
    return out


def derived(snap: dict) -> dict:
    """The counter-derived rates the BENCH payload carries (warn-only in
    benchmarks/compare.py; throughput stays the hard gate)."""
    batches = snap.get("engine.batches", 0)
    taken = snap.get("engine.fast.taken", 0)
    slow_batches = batches - taken
    return {
        "hit_rate_fast": taken / batches if batches else 0.0,
        "eligible_rate": (snap.get("engine.fast.eligible", 0) / batches
                          if batches else 0.0),
        "mean_slow_rounds": (snap.get("engine.rounds.slow", 0) / slow_batches
                             if slow_batches else 0.0),
    }
