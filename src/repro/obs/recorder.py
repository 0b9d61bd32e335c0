"""The executor's host counters and issue latencies (DESIGN.md §10).

The executor already journals everything worth counting — per-issue
`IssueRec`s, round boundaries, checkpoint/restore, shard-loss recoveries,
preempt drains, watchdog flags.  `Recorder` is the sink those hooks feed:
per-round issue-latency bookkeeping — the input to
`runtime.stragglers.StragglerWatchdog` — plus event counts (`metrics()`).
The executor's timeline is the profiler's: its spans are
`jax.profiler.TraceAnnotation`s (`runtime/executor.py`).

The Recorder is pure host-side python: it never touches jax and costs a
few dict writes per issue.
"""

from __future__ import annotations

import time


class Recorder:
    """Collects executor events.

    clock: seconds-returning monotonic clock (injectable for tests).
    """

    def __init__(self, *, clock=time.perf_counter):
        self.clock = clock
        self.counts: dict[str, int] = {}
        self.flags: list[tuple[int, list[int]]] = []  # (round, streams)
        # Issue-latency bookkeeping (the watchdog's input): latest latency
        # per stream this round, and the last-known latency per stream ever.
        self._round_lat: dict[int, float] = {}
        self._last_lat: dict[int, float] = {}

    def _bump(self, name: str, v: int = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + v

    # -- round / issue hooks (called by runtime.executor) ------------------

    def round_begin(self, round_idx: int) -> None:
        self._round_lat.clear()
        self._bump("exec.rounds")

    def round_end(self, round_idx: int) -> None:
        self._last_lat.update(self._round_lat)

    def issue_latency(self, stream_idx: int, seconds: float) -> None:
        """Record the host-side issue latency of one stream this round."""
        self._round_lat[stream_idx] = seconds
        self._bump("exec.issues")

    def retire(self) -> None:
        self._bump("exec.retires")

    def round_issued(self) -> bool:
        return bool(self._round_lat)

    def latency_vector(self, n_streams: int) -> list[float]:
        """Per-stream latencies for `StragglerWatchdog.observe`: streams
        quiet this round carry their last-known latency, streams never seen
        carry the fleet's current median (so they read as healthy)."""
        lats = sorted(self._round_lat.values())
        fill = lats[len(lats) // 2]
        return [self._last_lat.get(si, self._round_lat.get(si, fill))
                for si in range(n_streams)]

    def straggler_flags(self, round_idx: int, flagged) -> None:
        flagged = sorted(flagged)
        self.flags.append((round_idx, flagged))
        self._bump("exec.straggler_flags", len(flagged))

    # -- lifecycle events --------------------------------------------------

    def checkpoint(self, round_idx: int) -> None:
        self._bump("exec.checkpoints")

    def recovery(self, round_idx: int, shard: int, replayed: int,
                 latency_s: float) -> None:
        self._bump("exec.recoveries")
        self._bump("exec.replayed", replayed)

    def preempt(self, round_idx: int, drained: int) -> None:
        self._bump("exec.preempts")

    def data_fault(self, round_idx: int, kind: str, info: dict) -> None:
        self._bump("exec.data_faults")

    def scrub(self, round_idx: int, report) -> None:
        self._bump("exec.scrubs")
        self._bump("guard.cells_detected", len(report.detected))
        self._bump("guard.cells_repaired", len(report.repaired))
        self._bump("guard.cells_quarantined", len(report.quarantined))

    def shed(self, round_idx: int, stream: int, reason: str) -> None:
        self._bump("exec.shed")

    # -- output ------------------------------------------------------------

    def metrics(self) -> dict:
        """Host counter snapshot (merged with the in-graph counters by
        `obs.export.write_metrics_jsonl`)."""
        return dict(self.counts)
