"""The oversubscribed multi-stream executor (DESIGN.md §9).

The paper's throughput story needs MORE logical workers than hardware
slots: big atomics win when oversubscribed streams keep the engine's
fast path saturated while stalled streams wait out contention.  This
module is that regime as a scheduler:

  streams      S logical op streams (`runtime.streams`) share ONE
               big-atomic target.  Each scheduling round visits every
               live stream and issues at most one batch.
  in-flight    JAX async dispatch makes every issued round a future;
               the executor holds up to `slots * oversubscription`
               un-retired rounds, so stream i+1's host-side route/pack
               overlaps stream i's device round (donation keeps the
               double-buffer at two table allocations, `apply(donate=
               True)`).
  targets      `LocalTarget` wraps the single-device engine round
               (`engine.apply_round`); `DistTarget` wraps the mesh
               round (`distributed.apply_round`) — with `n_nodes > 1`
               the round routes hierarchically (intra-node combine,
               then ONE cross-node all_to_all), and the executor's
               overlap hides the cross-node hop behind other streams'
               host work.
  faults       `runtime.faults` injects delay / preempt / shard-loss
               at exact (round, issue) points.  Delays surface through
               the StragglerWatchdog (flagged streams skip their next
               issue slot); preemption drains, checkpoints and stops
               cleanly; shard loss discards in-flight rounds, restores
               the last round-boundary checkpoint, reshards onto the
               survivors (`elastic.reshard_dist` — versions preserved,
               so LL links survive) and replays the issue journal with
               the NEW geometry's claimed orders.
  history      every ops issue is journaled (stream, seq, ops, claimed
               order, delivered results); `tests/oracle.py`'s
               `replay_executor_history` replays the whole multi-stream
               interleaving — including across a recovery boundary —
               through one sequential oracle.
  timeline     each issue, recovery, scrub and checkpoint is a host span
               in the JAX profiler's trace (`executor.issue` with its
               stream's name, `executor.recover`, `executor.scrub`,
               `executor.checkpoint`); an ops issue's `atomics.apply`
               spans nest inside its `executor.issue`.

Nothing here blocks except retirement past the in-flight budget and the
explicit drains at checkpoint/recovery boundaries.
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import engine
from repro.obs.recorder import Recorder
from repro.runtime import faults


SPAN_ISSUE = "executor.issue"
SPAN_RECOVER = "executor.recover"
SPAN_SCRUB = "executor.scrub"
SPAN_CHECKPOINT = "executor.checkpoint"


def _ops_np(ops: engine.OpBatch) -> engine.OpBatch:
    return engine.OpBatch(*[np.array(x, copy=True) for x in ops])


def _ctx_np(ctx: engine.LinkCtx) -> engine.LinkCtx:
    return engine.LinkCtx(*[np.array(x, copy=True) for x in ctx])


# ---------------------------------------------------------------------------
# Targets: the shared big-atomic structure the streams contend on.
# ---------------------------------------------------------------------------

class LocalTarget:
    """Single-device table: rounds ride `engine.apply_round` with donation,
    so the in-flight window costs two table buffers, not `budget` of them."""

    kind = "local"

    def __init__(self, spec, initial=None):
        self.spec = spec
        self.state = engine.init(spec, initial)

    @property
    def width(self) -> int:
        return self.spec.n          # no lane cap beyond table size

    @property
    def n_shards(self) -> int:
        return 1

    def issue(self, ops, ctx, *, donate=True):
        h = engine.apply_round(self.spec, self.state, ops, ctx,
                               donate=donate)
        self.state = h.state
        return h

    def snapshot(self) -> dict:
        return {"logical": np.asarray(engine.logical(self.spec, self.state)),
                "versions": np.asarray(self.state.version)}

    def load(self, snap: dict) -> None:
        self.state = engine.init(self.spec, snap["logical"])._replace(
            version=np.asarray(snap["versions"], np.uint32))

    def shrink(self, n_surviving: int):
        raise RuntimeError("shard loss against a LocalTarget is fatal: "
                           "nothing to reshard onto")


class DistTarget:
    """Mesh-sharded table: rounds ride `distributed.apply_round` (flat or
    hierarchical per the DistSpec) with the claimed linearization computed
    up front; `shrink` reshards the live state onto a smaller mesh through
    `elastic.reshard_dist`, preserving values AND versions."""

    kind = "dist"

    def __init__(self, mesh, dspec, initial=None, *, mesh_factory=None):
        from repro.core import distributed as dist
        self._dist = dist
        self.mesh, self.dspec = mesh, dspec
        self.state = dist.init_dist(mesh, dspec, initial)
        # n_surviving -> (mesh, dspec): how to rebuild after shard loss
        self.mesh_factory = mesh_factory

    @property
    def width(self) -> int:
        return self.dspec.p_global

    @property
    def n_shards(self) -> int:
        return self.dspec.n_shards

    def issue(self, ops, ctx, *, donate=True):
        h = self._dist.apply_round(self.mesh, self.dspec, self.state, ops,
                                   ctx, with_order=True)
        self.state = h.state
        return h

    def snapshot(self) -> dict:
        return {"logical": np.asarray(self._dist.logical(self.dspec,
                                                         self.state)),
                "versions": np.asarray(self._dist.versions(self.dspec,
                                                           self.state))}

    def load(self, snap: dict) -> None:
        import jax
        from jax.sharding import NamedSharding
        st = self._dist.init_dist(self.mesh, self.dspec, snap["logical"])
        # splice the versions back (inverse of distributed.versions): LL
        # links restored alongside MUST see their pre-checkpoint versions
        local = st.local._replace(
            version=_split_versions(self.dspec, snap["versions"]))
        local = jax.device_put(
            local, NamedSharding(self.mesh, self._dist._pspec(self.dspec)))
        self.state = self._dist.DistState(local)

    def shrink(self, n_surviving: int) -> None:
        if self.mesh_factory is None:
            raise RuntimeError("shard loss needs mesh_factory= to rebuild "
                               "the mesh on the survivors")
        from repro.runtime.elastic import reshard_dist
        mesh, dspec = self.mesh_factory(n_surviving)
        self.state = reshard_dist(self.dspec, self.state, dspec, mesh)
        self.mesh, self.dspec = mesh, dspec


def _split_versions(dspec, vers):
    import jax.numpy as jnp
    s, nl = dspec.n_shards, dspec.n_local
    vers = np.asarray(vers, np.uint32)
    per = vers.reshape(nl, s).T if dspec.interleave else vers.reshape(s, nl)
    return jnp.asarray(np.ascontiguousarray(per))


# ---------------------------------------------------------------------------
# The issue journal / oracle history.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IssueRec:
    """One issued ops batch: everything `tests/oracle.py` needs to replay
    it, filled in two phases (order at issue, results at retire)."""

    stream: int
    seq: int
    ops: engine.OpBatch                    # numpy copies
    order: np.ndarray | None = None        # claimed order (None = lane order)
    overflow: np.ndarray | None = None
    value: np.ndarray | None = None
    success: np.ndarray | None = None


@dataclasses.dataclass
class Recovery:
    round: int
    shard: int
    n_shards: int          # surviving shard count
    replayed: int          # journaled batches re-issued
    latency_s: float


@dataclasses.dataclass
class StreamShed:
    """A stream dropped after exhausting its retry budget (graceful
    degradation: the run continues without it, DESIGN.md §11)."""
    stream: int
    round: int
    reason: str
    attempts: int


# ---------------------------------------------------------------------------
# The executor.
# ---------------------------------------------------------------------------

class Executor:
    """Schedule S streams against one target with more in-flight rounds
    than compute slots.

    target:           `LocalTarget` / `DistTarget` (None for pure
                      kind="host" stream sets, e.g. serving).
    streams:          `runtime.streams` objects (kinds "ops", "round",
                      "host" mix freely; "round" needs a LocalTarget).
    slots:            modeled compute slots per device.
    oversubscription: in-flight budget = slots * oversubscription; the
                      paper's regime is factor >= 4.
    watchdog:         `StragglerWatchdog(n_hosts=len(streams))`, fed the
                      per-stream issue latencies the Recorder keeps
                      (`Recorder.latency_vector`); flagged streams are
                      deprioritized (skip their next slot).
    recorder:         `obs.Recorder` sink for round/issue/lifecycle events
                      (a fresh one is built if omitted).  It owns the
                      event counts and the issue-latency bookkeeping
                      feeding the watchdog.
    guard:            `PreemptionGuard` (or compatible) polled at round
                      boundaries; `request_stop()` drains + checkpoints.
    injector:         `faults.FaultInjector`, polled before every issue
                      (scheduling faults) and at drained round boundaries
                      (data-plane faults, `poll_boundary`).
    checkpoint_dir /  atomic disk checkpoints (checkpoint/disk.py) every
    checkpoint_every  N rounds at a drained round boundary; an in-memory
                      copy always backs shard-loss recovery.
    retry_budget /    graceful degradation: a stream whose issue raises or
    backoff           whose every lane targets quarantined cells counts a
                      failed attempt, waits out `backoff.delay(attempts)`
                      rounds (sync/queue.BackoffPolicy), and is SHED with
                      a recorded reason once attempts exceed the budget —
                      the run continues without it.
    scrub_every       with BIGATOMIC_GUARD=on, run the integrity scrub
                      (guard/scrub.py) every N drained round boundaries
                      (default every boundary); repairs from the last
                      checkpoint, quarantines what it can't.  Guard off:
                      no scrubber object exists and issue paths are
                      byte-identical to the unguarded build.
    """

    def __init__(self, target, streams, *, slots: int = 2,
                 oversubscription: int = 2, watchdog=None, guard=None,
                 injector=None, checkpoint_dir: str | None = None,
                 checkpoint_every: int = 0, donate: bool = True,
                 recorder: Recorder | None = None, retry_budget: int = 3,
                 backoff=None, scrub_every: int = 1):
        self.target = target
        self.streams = list(streams)
        self.slots = slots
        self.oversubscription = oversubscription
        self.budget = max(1, slots * oversubscription)
        self.watchdog = watchdog
        self.guard = guard
        self.injector = injector
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.donate = donate
        self.recorder = recorder if recorder is not None else Recorder()

        self._inflight: deque = deque()
        self._ctx = {i: engine.init_ctx(s.width, self._k())
                     for i, s in enumerate(self.streams)
                     if s.kind == "ops"}
        self._seq = {i: 0 for i in range(len(self.streams))}
        self._round = 0
        self._skip: set[int] = set()
        self._delays: dict[int, list] = {}      # si -> [seconds, rounds left]
        self._last_ck = None                     # (payload, meta, hist_len)
        self.history: list[IssueRec] = []
        self.recoveries: list[Recovery] = []
        self.checkpoints: list[int] = []
        self.issues = 0
        self.deprioritized = 0
        self.stopped = False

        self.retry_budget = retry_budget
        if backoff is None:
            from repro.sync.queue import BackoffPolicy
            backoff = BackoffPolicy("exp", base=1, cap=8)
        self.backoff = backoff
        self.scrub_every = scrub_every
        self.shed: list[StreamShed] = []
        self._shed_set: set[int] = set()
        self._attempts: dict[int, int] = {}
        self._cooldown: dict[int, int] = {}      # si -> rounds to sit out
        self.data_faults: list = []              # (round, Fault, info)
        self.scrubber = None
        if target is not None:
            from repro import guard as _guard
            if _guard.enabled():
                spec = getattr(target, "spec", None)
                if spec is not None:
                    self.scrubber = _guard.Scrubber(spec)
                else:
                    d = target.dspec
                    self.scrubber = _guard.Scrubber(
                        d.inner, n=d.n_shards * d.n_local)

    def _k(self) -> int:
        if self.target is None:
            return 1
        spec = getattr(self.target, "spec", None) or self.target.dspec.inner
        return spec.k

    # -- issue / retire ------------------------------------------------------

    def _retire_one(self) -> None:
        rec, h, stream = self._inflight.popleft()
        self.recorder.retire()
        if hasattr(h, "finish"):                 # host-stream token
            h.finish()
            return
        h.wait()
        if rec is None:                          # "round" stream step
            return
        rec.value = np.asarray(h.result.value)
        rec.success = np.asarray(h.result.success)
        ovf = getattr(h, "overflow", None)
        rec.overflow = None if ovf is None else np.asarray(ovf)
        if self.scrubber is not None:
            self.scrubber.note_results(rec.ops, rec.success)
        stream.deliver(rec.seq, rec.value, rec.success, rec.overflow)

    def _drain(self) -> None:
        while self._inflight:
            self._retire_one()

    def _trim(self) -> None:
        while len(self._inflight) > self.budget:
            self._retire_one()

    def _issue(self, si: int, stream) -> bool:
        name = getattr(stream, "name", None) or f"s{si}"
        with TraceAnnotation(SPAN_ISSUE, stream=name):
            if not self._enqueue(si, stream):
                return False
        self.issues += 1
        self._trim()
        return True

    def _enqueue(self, si: int, stream) -> bool:
        """Issue one step of `stream` into the in-flight window; False if
        it had nothing to issue (or its issue failed and will retry)."""
        if stream.kind == "ops":
            ops = stream.next_batch()
            if ops is None:
                return False
            poisoned = None
            if self.scrubber is not None:
                # quarantined cells: lanes rewritten to IDLE pre-issue, so
                # they report success=False; the MASKED ops are journaled,
                # keeping oracle replay in bit-agreement
                ops, poisoned = self.scrubber.mask_ops(ops)
            seq = self._seq[si]
            self._seq[si] += 1
            try:
                h = self.target.issue(ops, self._ctx[si], donate=self.donate)
            except faults.ISSUE_FAULTS:
                # roll the stream back so the SAME batch retries after the
                # backoff window; non-seekable streams can't retry
                self._seq[si] = seq
                if not hasattr(stream, "seek"):
                    raise
                stream.seek(seq)
                self._note_failure(si, "issue raised")
                return False
            self._ctx[si] = h.ctx
            rec = IssueRec(si, seq, _ops_np(ops),
                           order=getattr(h, "order", None))
            self.history.append(rec)
            self._inflight.append((rec, h, stream))
            if poisoned is not None and \
                    not (np.asarray(ops.kind) != engine.IDLE).any():
                self._note_failure(si, "all lanes target quarantined cells")
            elif si in self._attempts:
                del self._attempts[si]          # progress resets the budget
        elif stream.kind == "round":
            if self.target.kind != "local":
                raise RuntimeError("round streams (MCAS) drive a "
                                   "LocalTarget")
            if stream.done():
                return False
            self.target.state = stream.step(self.target.spec,
                                            self.target.state)
            if self.scrubber is not None:
                # round streams mutate state outside the journal: the
                # scrubber can't attribute writes per-slot, so the whole
                # table goes dirty (quarantine-only until next checkpoint)
                self.scrubber.note_untracked()
            self._inflight.append((None, _CarryHandle(stream), None))
        elif stream.kind == "host":
            tok = stream.issue()
            if tok is None:
                return False
            self._inflight.append((None, tok, None))
        else:
            raise ValueError(f"unknown stream kind {stream.kind!r}")
        return True

    # -- faults --------------------------------------------------------------

    def _poll_faults(self, issues_in_round: int) -> None:
        if self.injector is None:
            return
        for f in self.injector.poll(self._round, issues_in_round):
            if f.kind == "delay":
                self._delays[f.stream] = [f.seconds, f.rounds]
            elif f.kind == "preempt":
                if self.guard is None:
                    from repro.runtime.preemption import PreemptionGuard
                    self.guard = PreemptionGuard()
                self.guard.request_stop()
            elif f.kind == "shard_loss":
                self._recover(f.shard)

    def _extra_delay(self, si: int) -> float:
        d = self._delays.get(si)
        return d[0] if d and d[1] > 0 else 0.0

    def _note_failure(self, si: int, reason: str) -> None:
        a = self._attempts.get(si, 0) + 1
        self._attempts[si] = a
        if a > self.retry_budget:
            self.shed.append(StreamShed(stream=si, round=self._round,
                                        reason=reason, attempts=a))
            self._shed_set.add(si)
            self._cooldown.pop(si, None)
            self.recorder.shed(self._round, si, reason)
        else:
            self._cooldown[si] = int(self.backoff.delay(a))

    def _guard_boundary(self) -> None:
        """Drained-round-boundary work: apply due data-plane faults, then
        scrub.  The baseline digest is taken AFTER the drain but BEFORE
        injection, so every boundary-injected bit flip / torn write is a
        guaranteed digest mismatch (see guard/scrub.py)."""
        if self.target is None:
            return
        due = self.injector.poll_boundary(self._round) \
            if self.injector is not None else []
        scrub_due = self.scrubber is not None and self.scrub_every \
            and self._round % self.scrub_every == 0
        if not due and not scrub_due:
            return
        self._drain()
        baseline = self.scrubber.digest_of(self.target) \
            if self.scrubber is not None else None
        for f, rng in due:
            self._apply_data_fault(f, rng)
        if self.scrubber is not None:
            with TraceAnnotation(SPAN_SCRUB):
                rep = self.scrubber.scrub(self.target, round_idx=self._round,
                                          baseline=baseline)
            self.recorder.scrub(self._round, rep)

    def _apply_data_fault(self, f, rng) -> None:
        from repro.guard.inject import (inject_snapshot_fault,
                                        inject_table_fault)
        if f.kind in ("bit_flip", "torn_write"):
            if self.target.kind == "local":
                self.target.state, info = inject_table_fault(
                    self.target.spec, self.target.state, f, rng)
            else:
                snap, info = inject_snapshot_fault(self.target.snapshot(),
                                                   f, rng)
                self.target.load(snap)
        elif f.kind == "stale_resurrect":
            if self._last_ck is None:
                return
            payload, meta, _ = self._last_ck
            self.target.load(payload["table"])
            info = {"kind": f.kind, "from_round": meta["round"]}
        elif f.kind in ("ckpt_corrupt", "ckpt_truncate"):
            info = self._damage_checkpoint(f, rng)
            if info is None:
                return                           # no disk checkpoint to hit
        else:
            raise ValueError(f"unknown data fault {f.kind!r}")
        self.data_faults.append((self._round, f, info))
        self.recorder.data_fault(self._round, f.kind, info)

    def _damage_checkpoint(self, f, rng):
        from repro.checkpoint.disk import list_steps
        if not self.checkpoint_dir:
            return None
        steps = list_steps(self.checkpoint_dir)
        if not steps:
            return None
        step = steps[-1]
        path = os.path.join(self.checkpoint_dir, f"step_{step:08d}")
        leaves = sorted(fn for fn in os.listdir(path)
                        if fn.endswith(".npy"))
        if not leaves:
            return None
        victim = os.path.join(path, leaves[int(rng.integers(len(leaves)))])
        size = os.path.getsize(victim)
        info = {"kind": f.kind, "step": step,
                "leaf": os.path.basename(victim)}
        if f.kind == "ckpt_truncate":
            with open(victim, "r+b") as fh:
                fh.truncate(size // 2)
            return info
        off = int(rng.integers(size))
        with open(victim, "r+b") as fh:
            fh.seek(off)
            byte = fh.read(1)[0]
            fh.seek(off)
            fh.write(bytes([byte ^ (1 << int(rng.integers(8)))]))
        info["offset"] = off
        return info

    # -- checkpoint / recovery ----------------------------------------------

    def _ck_payload(self) -> dict:
        return {"table": self.target.snapshot(),
                "ctx": {str(si): _ctx_np(ctx)._asdict()
                        for si, ctx in self._ctx.items()}}

    def checkpoint(self) -> None:
        """Drain and snapshot at a round boundary: the recovery point for
        shard loss (in-memory) and preemption resume (disk)."""
        with TraceAnnotation(SPAN_CHECKPOINT):
            self._drain()
            payload = self._ck_payload()
            meta = {"round": self._round,
                    "seq": {str(si): int(q) for si, q in self._seq.items()},
                    "n_shards": self.target.n_shards}
            self._last_ck = (payload, meta, len(self.history))
            if self.scrubber is not None:
                self.scrubber.set_checkpoint(payload["table"])
            if self.checkpoint_dir:
                from repro.checkpoint.disk import save_checkpoint
                save_checkpoint(self.checkpoint_dir, self._round, payload,
                                meta=meta)
            self.checkpoints.append(self._round)
            self.recorder.checkpoint(self._round)

    def _load_ck(self, payload: dict, meta: dict, hist_len: int) -> list:
        """Common restore: state, ctxs, seqs, stream cursors; returns the
        journal suffix (stream, seq) pairs issued after the checkpoint."""
        journal = [(r.stream, r.seq) for r in self.history[hist_len:]]
        del self.history[hist_len:]
        self.target.load(payload["table"])
        for key, c in payload["ctx"].items():
            self._ctx[int(key)] = engine.LinkCtx(**{
                f: np.asarray(v) for f, v in dict(c).items()})
        for key, q in meta["seq"].items():
            si = int(key)
            self._seq[si] = int(q)
            if hasattr(self.streams[si], "seek"):   # ops streams only
                self.streams[si].seek(int(q))
        return journal

    def _recover(self, shard: int) -> None:
        """Shard-loss recovery: discard in-flight, restore the last
        checkpoint, reshard onto the survivors, replay the journal in its
        recorded interleaving (deliveries are idempotent by seq — results
        issued after the checkpoint were provisional)."""
        if self._last_ck is None:
            raise RuntimeError("shard loss before the first checkpoint")
        with TraceAnnotation(SPAN_RECOVER):
            t0 = time.perf_counter()
            self._inflight.clear()                  # results may span the loss
            payload, meta, hist_len = self._last_ck
            journal = self._load_ck(payload, meta, hist_len)
            n_surviving = self.target.n_shards - 1
            self.target.shrink(n_surviving)
            for si, seq in journal:
                assert self._seq[si] == seq, (si, self._seq[si], seq)
                self._issue(si, self.streams[si])
            self._drain()
            # the post-recovery state is the new baseline
            self.checkpoint()
            rec = Recovery(self._round, shard, self.target.n_shards,
                           len(journal), time.perf_counter() - t0)
        self.recoveries.append(rec)
        self.recorder.recovery(rec.round, shard, rec.replayed, rec.latency_s)

    def resume(self, checkpoint_dir: str | None = None) -> int:
        """Resume from the newest VERIFYING disk checkpoint (preemption
        restart): restores table state + link ctxs + stream cursors;
        `run()` then continues bit-identically with the pre-preemption
        schedule.  A corrupt or truncated newest step is skipped —
        `checkpoint.restore_latest` falls back CRC-verified step by step
        (DESIGN.md §11)."""
        from repro.checkpoint import disk
        ckdir = checkpoint_dir or self.checkpoint_dir
        template = self._ck_payload()
        payload, meta, _step = disk.restore_latest(ckdir, template)
        self._load_ck(payload, meta, len(self.history))
        self._round = int(meta["round"])
        self._last_ck = (payload, meta, len(self.history))
        if self.scrubber is not None:
            self.scrubber.set_checkpoint(payload["table"])
        return self._round

    # -- the scheduling loop -------------------------------------------------

    def _live_streams(self):
        return [s for si, s in enumerate(self.streams)
                if si not in self._shed_set]

    def done(self) -> bool:
        return all(s.done() for s in self._live_streams()) \
            and not self._inflight

    def _run_round(self) -> None:
        self._round += 1
        rcd = self.recorder
        rcd.round_begin(self._round)
        issued = 0
        for si, stream in enumerate(self.streams):
            self._poll_faults(issued)
            if self.guard is not None and self.guard.should_stop:
                return
            if si in self._shed_set or stream.done():
                continue
            cd = self._cooldown.get(si, 0)
            if cd > 0:
                self._cooldown[si] = cd - 1     # backoff: sit out the round
                continue
            if si in self._skip:
                self._skip.discard(si)          # deprioritized: skip ONE slot
                continue
            t0 = rcd.clock()            # injectable (obs.Recorder(clock=))
            if self._issue(si, stream):
                issued += 1
                rcd.issue_latency(si, rcd.clock() - t0
                                  + self._extra_delay(si))
        if not issued and self._inflight:
            # nothing issuable until in-flight work retires (e.g. a decode
            # whose successor needs its tokens): guarantee progress
            self._retire_one()
        self._poll_faults(issued)
        for d in self._delays.values():
            d[1] -= 1
        rcd.round_end(self._round)
        if self.watchdog is not None and rcd.round_issued():
            plan = self.watchdog.observe(
                rcd.latency_vector(len(self.streams)))
            if plan.flagged:
                rcd.straggler_flags(self._round, plan.flagged)
                self._skip |= set(plan.flagged)
                self.deprioritized += len(plan.flagged)

    def run(self, max_rounds: int = 10_000):
        """Drive every stream to completion (or a clean preempted stop);
        returns `self.report()`."""
        if self.target is not None and self._last_ck is None \
                and not self.history:
            self.checkpoint()                   # round-0 recovery baseline
        while not all(s.done() for s in self._live_streams()):
            if self._round >= max_rounds:
                raise RuntimeError(f"executor exceeded {max_rounds} rounds")
            self._run_round()
            self._guard_boundary()
            if self.guard is not None and self.guard.should_stop:
                self.recorder.preempt(self._round,
                                      drained=len(self._inflight))
                if self.target is not None:
                    self.checkpoint()
                else:
                    self._drain()
                self.stopped = True
                return self.report()
            if self.checkpoint_every and self.target is not None \
                    and self._round % self.checkpoint_every == 0:
                self.checkpoint()
        self._drain()
        return self.report()

    def report(self) -> dict:
        return {
            "rounds": self._round,
            "issues": self.issues,
            "streams": len(self.streams),
            "budget": self.budget,
            "stopped": self.stopped,
            "deprioritized": self.deprioritized,
            "checkpoints": list(self.checkpoints),
            "recoveries": [dataclasses.asdict(r) for r in self.recoveries],
            "faults_fired": [dataclasses.asdict(f) for f in
                             (self.injector.fired if self.injector else [])],
            "shed": [dataclasses.asdict(s) for s in self.shed],
            "data_faults": [{"round": r, **info}
                            for r, _f, info in self.data_faults],
            "scrubs": [rep.to_json() for rep in
                       (self.scrubber.reports if self.scrubber else [])],
            "poisoned": int(self.scrubber.poison.sum())
            if self.scrubber else 0,
            "events": self.recorder.metrics(),
        }


class _CarryHandle:
    """Retirement handle for a "round" stream step: blocks on the carry."""

    __slots__ = ("_stream",)

    def __init__(self, stream):
        self._stream = stream

    def wait(self):
        import jax
        jax.block_until_ready(jax.tree_util.tree_leaves(self._stream.carry))
