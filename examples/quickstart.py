"""Quickstart: the big-atomic table API in 60 lines.

  PYTHONPATH=src python examples/quickstart.py

Creates a table of 1024 big atomics of 4 words each (strategy: the paper's
Cached-Memory-Efficient), runs batched load/store/CAS against it, shows the
torn-writer resilience that motivates the whole design, and finishes with a
CacheHash insert/find/delete round-trip.
"""

import numpy as np
import jax.numpy as jnp

from repro.core import semantics as sem
from repro.core.bigatomic import BigAtomicTable, begin_update, read_protocol
from repro.core.cachehash import CacheHash
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

# --- a table of 1024 cells x 4 words, Cached-Memory-Efficient --------------
table = BigAtomicTable(n=1024, k=4, strategy="cached_me", p_max=256)

# batched stores: lanes are the "threads" of one linearized step
slots = np.arange(8)
values = np.arange(32, dtype=np.uint32).reshape(8, 4)
table.store(slots, values)
print("loaded:", np.asarray(table.load(slots[:3])))

# batched CAS: succeeds only where `expected` matches
expected = values[:3].copy()
expected[1] += 99                                  # lane 1 will fail
desired = values[:3] + 1000
res, stats, traffic = table.cas(slots[:3], expected, desired)
print("cas success:", np.asarray(res.success))     # [True, False, True]
print("rounds:", int(stats.rounds), "| modeled bytes:",
      float(traffic.bytes_read + traffic.bytes_written))

# --- the paper's point: a stalled writer doesn't hurt readers --------------
frozen = begin_update(table.state, slot=5, new_value=np.full(4, 7, np.uint32),
                      strategy="cached_me")        # writer stalls mid-copy
vals, ok = read_protocol(frozen, jnp.asarray([5]), strategy="cached_me")
print("read under torn writer: ok =", bool(ok[0]),
      "value =", np.asarray(vals[0]), "(consistent NEW value, no blocking)")

# --- CacheHash: the §4 hash table with inlined first links -----------------
h = CacheHash(nb=256, vw=2, strategy="cached_me")
keys = np.asarray([11, 22, 33], np.uint32)
vals = np.asarray([[1, 2], [3, 4], [5, 6]], np.uint32)
h.insert(keys, vals)
res, stats = h.find(keys)
print("find:", np.asarray(res.found), np.asarray(res.value))
print("inline hits:", int(stats.inline_hits), "of 3 (one cell access each)")
h.delete(keys[:1])
res, _ = h.find(keys)
print("after delete:", np.asarray(res.found))

# --- observability: the §10 counters, on demand ----------------------------
# BIGATOMIC_OBS=off (the default) costs nothing — the jitted programs are
# byte-identical.  Flip it to "counters" and every engine call accumulates
# the in-graph telemetry; pull it any time with obs.snapshot():
import os

os.environ["BIGATOMIC_OBS"] = "counters"
import repro.obs as obs

obs.reset()
table.store(slots, values)
table.cas(slots[:3], expected, desired)
snap = obs.snapshot()          # flat {metric_name: int}, stable schema
rates = obs.derived(snap)      # hit_rate_fast / eligible_rate / mean_slow_rounds
print("engine.batches:", snap["engine.batches"],
      "| fast-path hit rate:", round(rates["hit_rate_fast"], 2),
      "| cas failures:", snap["engine.fail.cas"])
# The executor timeline tier: pass obs.Recorder(trace=True) to
# runtime.Executor and export with obs.write_chrome_trace(rcd, path) —
# one Perfetto track per logical stream, one per device slot.  The full
# metric-name table lives in DESIGN.md §10.
os.environ.pop("BIGATOMIC_OBS")

# --- fault tolerance: the §11 guard, on demand -----------------------------
# BIGATOMIC_GUARD=off (the default) costs nothing.  The guard layer gives
# you a per-cell integrity digest, a scrub pass that detects/repairs/
# quarantines corruption, and a seeded injector to prove it works:
from repro import guard
from repro.guard.inject import inject_table_fault
from repro.runtime.faults import Fault

baseline = np.asarray(guard.cell_digest(table.spec, table.state))
corrupt, info = inject_table_fault(                 # flip one random bit
    table.spec, table.state, Fault(round=1, kind="bit_flip"),
    np.random.default_rng(0))
report = guard.scrub(table.spec, corrupt, baseline=baseline)
print("injected", info["kind"], "at slot", info["slot"],
      "-> detected:", sorted(report.detected),
      "| quarantined:", sorted(report.quarantined))
# Under runtime.Executor(scrub_every=1, retry_budget=...) the scrub runs
# automatically at round boundaries, repairs cells with a trusted copy,
# masks ops against quarantined cells (success=False), and sheds streams
# that exhaust their retry budget instead of crashing the run; the
# serving engine's OverloadPolicy sheds admissions the same way.  The
# chaos gate (`python -m repro.guard.chaos`) replays seeded fault
# schedules through the sequential oracle — see DESIGN.md §11.
