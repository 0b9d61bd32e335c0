"""Whole-table integrity scrub: digest + invariants → repair/quarantine.

The scrub pass runs at drained round boundaries (no batch in flight) and
classifies every cell:

  clean        digest matches the pre-boundary baseline and every
               structural invariant holds.
  repairable   corruption detected AND the cell has not been written
               since the last in-memory checkpoint — the checkpoint's
               (logical, version) pair is still the truth, so the cell
               is spliced back and the target reloads (a full layout
               rebuild, which also restores indirect/cached internals).
  quarantined  corruption detected on a cell that WAS written since the
               checkpoint (or before any checkpoint exists): no trusted
               copy survives, so the cell is poisoned.  Subsequent ops
               against it are rewritten to IDLE before issue and report
               `success=False` — the overflow-mask contract extended to
               integrity, never silently serving garbage.

Detection is a per-cell FNV-1a digest over the cell's LOGICAL value row
plus its version word.  Each FNV step `h -> (h ^ w) * PRIME` is a
bijection of `h` for fixed `w` (PRIME is odd), so any single-cell change
to any word yields a different digest — boundary-injected bit flips and
torn writes are detected with probability 1, not 1 - 2^-32.  Structural
invariants (guard/invariants.py) catch corruption the logical plane
can't see (cached_wf backup flips, bptr damage).

Two lowering paths compute the digest, per ISSUE: the XLA twin always
exists; where the strategy lowers the engine round to Pallas
(`lower_round` overridden) and BIGATOMIC_ENGINE_KERNEL resolves to
"pallas", a blocked Pallas pass computes the same digest (equality is
pinned by tests/test_guard.py).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import CAS, IDLE, SC, STORE
from repro.core.registry import StrategyImpl, get_strategy
from repro.guard import invariants as _inv

FNV_OFFSET = np.uint32(2166136261)
FNV_PRIME = np.uint32(16777619)


# ---------------------------------------------------------------------------
# digest: XLA twin + blocked Pallas pass
# ---------------------------------------------------------------------------

def _digest_xla(vals, ver):
    h = jnp.full(ver.shape, FNV_OFFSET, jnp.uint32)
    for j in range(vals.shape[1]):
        h = (h ^ vals[:, j]) * FNV_PRIME
    return (h ^ ver) * FNV_PRIME


def digest_np(logical, versions) -> np.ndarray:
    """Numpy twin of the digest, for snapshot-plane (DistTarget) scrubs."""
    vals = np.asarray(logical, np.uint32)
    ver = np.asarray(versions, np.uint32)
    h = np.full(ver.shape, FNV_OFFSET, np.uint32)
    with np.errstate(over="ignore"):
        for j in range(vals.shape[1]):
            h = (h ^ vals[:, j]) * FNV_PRIME
        h = (h ^ ver) * FNV_PRIME
    return h


def _digest_pallas(vals, ver, *, interpret: bool):
    """Blocked Pallas digest, one cell per lane.

    Cells run along lanes so that the word-serial FNV chain is a vector
    op over a block of cells.  For k < 128 the device already keeps
    `vals` column-major, so `vals.T` is a bitcast; wider rows are
    transposed block by block in the kernel.  (Taking `vals` as [n, k]
    and `ver` as [n, 1] would re-lay both into copies padded to 128
    lanes.)"""
    from jax.experimental import pallas as pl
    n, k = vals.shape
    cols = k < 128
    # Blocks of <= 2^18 words, whole 128-lane vregs, no wider than the
    # (padded) table.
    block = min(max(128, (2 ** 18 // k) // 128 * 128), 8192,
                -(-n // 128) * 128)
    pad = (-n) % block
    if pad:
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        ver = jnp.pad(ver, (0, pad))

    def kernel(vals_ref, ver_ref, out_ref):
        v = vals_ref[...] if cols else vals_ref[...].T
        h = jnp.full((1, block), FNV_OFFSET, jnp.uint32)
        for j in range(k):
            h = (h ^ v[j:j + 1, :]) * FNV_PRIME
        out_ref[...] = (h ^ ver_ref[...]) * FNV_PRIME

    row = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // block,),
        in_specs=[pl.BlockSpec((k, block), lambda i: (0, i)) if cols
                  else pl.BlockSpec((block, k), lambda i: (i, 0)),
                  row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.uint32),
        interpret=interpret,
    )(vals.T if cols else vals, ver.reshape(1, n + pad))
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("spec", "mode", "interpret"))
def _cell_digest(spec, state, mode: str, interpret: bool):
    impl = get_strategy(spec.strategy)
    vals = jnp.asarray(impl.logical(state), jnp.uint32)
    ver = jnp.asarray(state.version, jnp.uint32)
    if mode == "pallas":
        return _digest_pallas(vals, ver, interpret=interpret)
    return _digest_xla(vals, ver)


def cell_digest(spec, state, *, mode: str | None = None):
    """uint32[n] FNV-1a digest of each cell's (logical row, version).

    mode None defers to BIGATOMIC_ENGINE_KERNEL (kernels/engine_round
    resolution): the Pallas pass is used only where the strategy already
    lowers the engine round — same eligibility rule as the fused round."""
    from repro.kernels import engine_round
    resolved, interpret = engine_round.resolved_mode(mode)
    impl = get_strategy(spec.strategy)
    lowers = type(impl).lower_round is not StrategyImpl.lower_round
    use = "pallas" if (resolved == "pallas" and lowers) else "xla"
    return _cell_digest(spec, state, use, interpret)


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScrubReport:
    """One scrub pass's classification (slot lists are global indices)."""
    round: int
    strategy: str
    n: int
    digest_checked: bool                  # had a pre-boundary baseline
    digest_mismatch: list
    invariant_violations: dict            # name -> [slots]
    detected: list                        # newly-anomalous, not yet poisoned
    contained: list                       # anomalous but already quarantined
    repaired: list
    quarantined: list
    poisoned_total: int
    latency_s: float

    @property
    def clean(self) -> bool:
        return not self.detected and not self.contained

    def to_json(self) -> dict:
        out = dataclasses.asdict(self)
        out["clean"] = self.clean
        return out


def _mask_slots(mask) -> list:
    return np.flatnonzero(np.asarray(mask)).tolist()


def scrub(spec, state, *, baseline=None, round_idx: int = 0) -> ScrubReport:
    """Standalone detection-only scrub of a quiescent LocalTarget state.

    `baseline`: uint32[n] digest from `cell_digest` taken at an earlier
    trusted point; None skips the digest check (invariants only)."""
    t0 = time.perf_counter()
    inv = {name: _mask_slots(m)
           for name, m in _inv.check_invariants(spec, state).items()
           if np.asarray(m).any()}
    mismatch = []
    if baseline is not None:
        mismatch = _mask_slots(
            np.asarray(cell_digest(spec, state)) != np.asarray(baseline))
    detected = sorted(set(mismatch).union(*inv.values()) if inv
                      else set(mismatch))
    return ScrubReport(
        round=round_idx, strategy=spec.strategy, n=spec.n,
        digest_checked=baseline is not None, digest_mismatch=mismatch,
        invariant_violations=inv, detected=detected, contained=[],
        repaired=[], quarantined=detected, poisoned_total=len(detected),
        latency_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# executor-side scrubber: baseline digests, dirty tracking, repair
# ---------------------------------------------------------------------------

class Scrubber:
    """Owns the guard state the executor threads through a run: the sticky
    poison mask, dirty-since-checkpoint tracking (what repair may touch),
    and the last checkpoint's logical plane (what repair splices from)."""

    def __init__(self, spec, *, n: int | None = None):
        self.spec = spec
        self.n = spec.n if n is None else n
        self.poison = np.zeros((self.n,), bool)
        self.dirty = np.ones((self.n,), bool)   # no checkpoint yet: all dirty
        self._ckpt = None                       # {"logical","versions"}
        self.reports: list[ScrubReport] = []

    # -- baseline / bookkeeping -------------------------------------------
    def digest_of(self, target) -> np.ndarray:
        if target.kind == "local":
            return np.asarray(cell_digest(target.spec, target.state))
        snap = target.snapshot()
        return digest_np(snap["logical"], snap["versions"])

    def set_checkpoint(self, table_snap: dict) -> None:
        """A round-boundary checkpoint was taken: it becomes repair truth
        and every cell becomes clean-relative-to-it."""
        self._ckpt = {
            "logical": np.array(table_snap["logical"], np.uint32, copy=True),
            "versions": np.array(table_snap["versions"], np.uint32,
                                 copy=True)}
        self.dirty[:] = False

    def note_results(self, ops, success) -> None:
        """Mark cells written by a retired batch dirty (STORE/CAS/SC that
        reported success — failed writes don't move the cell)."""
        kind = np.asarray(ops.kind)
        wrote = np.isin(kind, (STORE, CAS, SC)) & np.asarray(success, bool)
        if wrote.any():
            self.dirty[np.asarray(ops.slot)[wrote]] = True

    def note_untracked(self) -> None:
        """A mutation the journal can't attribute per-slot (round streams'
        direct state steps): conservatively dirty the whole table."""
        self.dirty[:] = True

    # -- poison contract ---------------------------------------------------
    def mask_ops(self, ops):
        """Rewrite lanes aimed at quarantined cells to IDLE; returns
        (masked_ops, bool[q] poisoned-lane mask or None).  The MASKED ops
        are what gets issued AND journaled, so oracle replay agrees that
        those lanes report success=False."""
        kind = np.asarray(ops.kind)
        slot = np.asarray(ops.slot)
        bad = self.poison[np.clip(slot, 0, self.n - 1)] & (kind != IDLE)
        if not bad.any():
            return ops, None
        masked = ops._replace(
            kind=np.where(bad, IDLE, kind).astype(kind.dtype))
        return masked, bad

    # -- the pass ----------------------------------------------------------
    def scrub(self, target, *, round_idx: int, baseline) -> ScrubReport:
        t0 = time.perf_counter()
        if target.kind == "local":
            inv_masks = _inv.check_invariants(target.spec, target.state)
            digest = np.asarray(cell_digest(target.spec, target.state))
        else:
            snap = target.snapshot()
            # snapshot plane: parity is the one invariant visible globally
            inv_masks = {"version_parity": snap["versions"] % 2 != 0}
            digest = digest_np(snap["logical"], snap["versions"])

        anomaly = np.zeros((self.n,), bool)
        inv = {}
        for name, m in inv_masks.items():
            m = np.asarray(m)
            if m.any():
                inv[name] = _mask_slots(m)
                anomaly |= m
        mismatch = np.zeros((self.n,), bool)
        if baseline is not None:
            mismatch = digest != np.asarray(baseline)
            anomaly |= mismatch

        detected = anomaly & ~self.poison
        contained = anomaly & self.poison
        repairable = detected & ~self.dirty if self._ckpt is not None \
            else np.zeros((self.n,), bool)
        quarantine = detected & ~repairable

        if detected.any():
            snap = target.snapshot()
            logical = np.array(snap["logical"], np.uint32, copy=True)
            versions = np.array(snap["versions"], np.uint32, copy=True)
            if repairable.any():
                logical[repairable] = self._ckpt["logical"][repairable]
                versions[repairable] = self._ckpt["versions"][repairable]
            # full reload even when nothing was repairable: init rebuilds
            # the layout (pointers, pool, parity) consistently, so a
            # quarantined cell is structurally sound — just untrusted
            target.load({"logical": logical, "versions": versions})
            self.poison |= quarantine

        report = ScrubReport(
            round=round_idx,
            strategy=getattr(self.spec, "strategy", "?"), n=self.n,
            digest_checked=baseline is not None,
            digest_mismatch=_mask_slots(mismatch),
            invariant_violations=inv,
            detected=_mask_slots(detected),
            contained=_mask_slots(contained),
            repaired=_mask_slots(repairable),
            quarantined=_mask_slots(quarantine),
            poisoned_total=int(self.poison.sum()),
            latency_s=time.perf_counter() - t0)
        self.reports.append(report)
        return report
