"""Smoke run of the big-atomics system on a TPU, at a deployment's size.

  python chip_smoke.py [--seed S]              # one chip
  python chip_smoke.py --chips 4 [--seed S]    # the sharded path, 4 chips

One chip drives the main path through the entry points users call and
checks every result against the repository's sequential oracles:

  device   the first device is a TPU (no CPU continuation);
  table    for seqlock / indirect / cached_wf / cached_me, a 2^24-cell,
           4-word table under `atomics.apply`: a collision-free mixed
           LOAD/STORE/CAS batch (fast tier), a Zipf-0.99 batch (slow tier)
           and an LL -> SC/VALIDATE round, against `TableOracle`; the
           compiled round must hold the Pallas kernel and need no more
           temporary device memory than the table state;
  hash     a 2^22-bucket CacheHash loaded with 2^21 keys, then a 95/3/2
           FIND/INSERT/DELETE mix over Zipf-0.99 keys, against
           `cachehash.apply_reference`;
  mcas     1024 width-4 transactions on the cached_me table, against
           `mcas_reference`;
  serving  `ServingEngine` on deepseek-7b at its published widths, cut to
           2 layers, bf16: 3 requests of 64-token prompts, 16 new tokens
           each, under two page-table strategies (identical token streams),
           first decode step's logits against the plain full-sequence
           `forward`.

`--chips 4` runs only the sharded path and its comparison: the same
2^24-cell table and a CacheHash sharded over a 4-device mesh, each batch
checked against single-device `atomics.apply` / `apply_hash` on the same
ops in the order the sharded round claims.

Earlier lines report each phase; the last line is one JSON object naming
the device.  A failed check raises, and the script exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")]

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax import monitoring                                     # noqa: E402

TABLE_N, TABLE_K, TABLE_P = 2 ** 24, 4, 8192
STRATEGIES = ("seqlock", "indirect", "cached_wf", "cached_me")
HASH_NB, HASH_VW, HASH_KEYS = 2 ** 22, 2, 2 ** 21
MIX = (0.95, 0.03, 0.02)                 # FIND / INSERT / DELETE
MIX_BATCHES = 4
TXNS, TXN_W = 1024, 4
ZIPF = 0.99
PROMPT, NEW_TOKENS, REQUESTS = 64, 16, 3
SERVE_STRATEGIES = ("cached_me", "seqlock")
# bf16 tolerance for decode logits against the full-sequence forward:
# |decode - forward| <= LOGIT_ATOL + LOGIT_RTOL * max|forward| per request.
LOGIT_ATOL, LOGIT_RTOL = 0.05, 0.02


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

_compile_s = [0.0]
_START = time.perf_counter()


def _on_duration(name: str, secs: float, **_):
    if name.startswith("/jax/core/compile/"):
        _compile_s[0] += secs


@contextlib.contextmanager
def phase(name: str, table_bytes: int = 0):
    """Print one line for the phase: wall time, compile time (tracing,
    lowering and backend compile), seconds since the script started, the
    device's peak bytes in use so far and the bytes of the state the phase
    works on."""
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": name, "wall_s": time.perf_counter() - t0,
        "compile_s": _compile_s[0] - c0,
        "since_start_s": time.perf_counter() - _START,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "table_bytes": table_bytes}), flush=True)


def nbytes(tree) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(tree))


def check_compiled(what: str, compiled, state_bytes: int) -> None:
    """The compiled round runs the Pallas kernel, not a reference, and
    needs no more temporary device memory than the state it updates."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(json.dumps({"compiled": what, "temp_bytes": temp,
                      "state_bytes": state_bytes}), flush=True)
    require("tpu_custom_call" in compiled.as_text(),
            f"{what}: no Pallas kernel in the compiled round")
    require(temp <= state_bytes, what, temp, state_bytes)


def require(ok, *what) -> None:
    """A check that holds under `python -O` too."""
    if not ok:
        raise AssertionError(what)


def check_equal(what: str, got, want) -> None:
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=what)


# ---------------------------------------------------------------------------
# Workloads
# ---------------------------------------------------------------------------

@functools.cache
def _zeta(n_items: int, theta: float) -> float:
    return float(np.sum(np.arange(1, n_items + 1, dtype=np.float64)
                        ** -theta))


def zipf_ranks(rng, n_items: int, size: int, theta: float = ZIPF):
    """Zipf(theta) ranks in [0, n_items), rank 0 the hottest (Gray et al.,
    SIGMOD 1994, as YCSB's ZipfianGenerator draws them)."""
    zetan = _zeta(n_items, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / n_items) ** (1 - theta)) / (1 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    tail = (n_items * (eta * u - eta + 1) ** alpha).astype(np.int64)
    ranks = np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))
    return np.minimum(ranks, n_items - 1)


def scatter(ranks, n_items: int):
    """Spread ranks over [0, n_items) (a power of two) by an odd multiply,
    a bijection, so hot items do not sit side by side."""
    return ((ranks.astype(np.uint64) * 0x9E3779B1) % n_items).astype(np.int32)


def table_ops(rng, kind, slot, current, k: int):
    """A batch with half the CAS comparands equal to the cell's value."""
    from repro import atomics
    p = kind.shape[0]
    expected = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    use_cur = rng.random(p) < 0.5
    expected = np.where(use_cur[:, None], current[slot], expected)
    desired = rng.integers(0, 2 ** 32, (p, k), dtype=np.uint32)
    return atomics.make_ops(kind, slot, expected, desired, k=k)


def table_batches(rng, n: int, k: int, p: int, current):
    """(name, ops, fast tier expected) for the table phase; the LL -> SC /
    VALIDATE pair shares its slots."""
    from repro import atomics
    rw = np.asarray([atomics.LOAD, atomics.STORE, atomics.CAS], np.int32)
    mixed = table_ops(rng, rng.choice(rw, p),
                      rng.choice(n, p, replace=False).astype(np.int32),
                      current, k)
    zslot = scatter(zipf_ranks(rng, n, p), n)
    zipf = table_ops(rng, rng.choice(rw, p), zslot, current, k)
    lslot = scatter(zipf_ranks(rng, n, p), n)
    ll = atomics.make_ops(np.full(p, atomics.LL, np.int32), lslot, k=k)
    scv = np.where(rng.random(p) < 0.75, atomics.SC,
                   atomics.VALIDATE).astype(np.int32)
    sc = table_ops(rng, scv, lslot, current, k)
    return [("mixed", mixed, True), ("zipf", zipf, False), ("ll", ll, True),
            ("sc_validate", sc, False)]


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def run_table(seed: int, strategy: str, n: int = TABLE_N, k: int = TABLE_K,
              p: int = TABLE_P):
    """One strategy through `atomics.apply`; returns (spec, state, oracle)
    for the MCAS phase."""
    from oracle import TableOracle
    from repro import atomics
    from repro.core import engine
    from repro.kernels import engine_round

    spec = atomics.AtomicSpec(n, k, strategy, p_max=p)
    initial = jax.random.bits(jax.random.PRNGKey(seed), (n, k), jnp.uint32)
    state = atomics.init(spec, initial)
    oracle = TableOracle(n, k, p, initial=np.asarray(initial))
    del initial
    state_bytes = nbytes(state)
    rng = np.random.default_rng(seed)
    ctx = atomics.init_ctx(p, k)
    batches = table_batches(rng, n, k, p, oracle.data)

    with phase(f"table/{strategy}/compile", state_bytes):
        compiled = engine._apply.lower(
            spec, state, batches[0][1], ctx,
            engine_round.configured_mode(), None).compile()
        check_compiled(f"table/{strategy}", compiled, state_bytes)

    for name, ops, fast in batches:
        with phase(f"table/{strategy}/{name}", state_bytes):
            took_fast = bool(engine_round.fast_path_ok(n, ops))
            require(took_fast == fast, name, "fast tier", took_fast)
            state, ctx, res, _stats, _ = atomics.apply(spec, state, ops, ctx)
            ref = oracle.step(ops)
            oracle.check(result=res, ref=ref, ctx=ctx,
                         msg=f"{strategy}/{name}")
            touched = np.unique(np.asarray(ops.slot))
            check_equal(f"{strategy}/{name}: touched values",
                        atomics.logical(spec, state)[touched],
                        oracle.data[touched])
            check_equal(f"{strategy}/{name}: touched versions",
                        state.version[touched], oracle.version[touched])
    with phase(f"table/{strategy}/whole_table", state_bytes):
        oracle.check(logical=atomics.logical(spec, state),
                     version=state.version, msg=f"{strategy}: whole table")
    return spec, state, oracle


def hash_ops(kind, keys, values, vw: int = HASH_VW):
    from repro.core import cachehash as ch
    return ch.make_hash_ops(np.asarray(kind, np.int32), keys, values, vw=vw)


def hash_keys(rng, count: int):
    """`count` distinct uint32 keys: an odd multiply plus offset of
    0..count-1 is a bijection of the uint32 ring."""
    mul = np.uint64(rng.integers(0, 2 ** 31) * 2 + 1)
    off = np.uint64(rng.integers(0, 2 ** 32))
    return ((np.arange(count, dtype=np.uint64) * mul + off)
            % (1 << 32)).astype(np.uint32)


def check_hash(what: str, res, ref) -> None:
    check_equal(f"{what}: found", res.found, ref.found)
    check_equal(f"{what}: value", res.value, ref.value)
    require(not np.asarray(res.overflow).any(), f"{what}: chain overflow")


def run_hash(seed: int, nb: int = HASH_NB, n_keys: int = HASH_KEYS,
             p: int = TABLE_P, mix_batches: int = MIX_BATCHES):
    from repro import atomics
    from repro.core import cachehash as ch

    spec = atomics.HashSpec(nb, HASH_VW, "cached_me", p_max=p, inline=True)
    state = ch.init_hash(spec)
    model: dict = {}
    rng = np.random.default_rng(seed + 1)
    keys = hash_keys(rng, n_keys)
    values = rng.integers(0, 2 ** 32, (n_keys, HASH_VW), dtype=np.uint32)
    state_bytes = nbytes(state)

    with phase("hash/load", state_bytes):
        insert = np.full(p, atomics.INSERT, np.int32)
        for i in range(0, n_keys, p):
            ops = hash_ops(insert, keys[i:i + p], values[i:i + p])
            state, res, _ = ch.apply_hash(spec, state, ops)
            model, ref = ch.apply_reference(model, ops, HASH_VW)
            check_hash(f"hash/load {i // p}", res, ref)
        require(int(state.count) == len(model) == n_keys, "hash/load count")

    with phase("hash/mix", state_bytes):
        kinds = np.asarray([atomics.FIND, atomics.INSERT, atomics.DELETE],
                           np.int32)
        for b in range(mix_batches):
            kind = rng.choice(kinds, p, p=MIX)
            key = keys[scatter(zipf_ranks(rng, n_keys, p), n_keys)]
            val = rng.integers(0, 2 ** 32, (p, HASH_VW), dtype=np.uint32)
            ops = hash_ops(kind, key, val)
            state, res, _ = ch.apply_hash(spec, state, ops)
            model, ref = ch.apply_reference(model, ops, HASH_VW)
            check_hash(f"hash/mix {b}", res, ref)
        require(int(state.count) == len(model), "hash/mix count")

    with phase("hash/sweep", state_bytes):
        find = np.full(p, atomics.FIND, np.int32)
        zeros = np.zeros((p, HASH_VW), np.uint32)
        for i in range(0, n_keys, p):
            ops = hash_ops(find, keys[i:i + p], zeros)
            state, res, _ = ch.apply_hash(spec, state, ops)
            model, ref = ch.apply_reference(model, ops, HASH_VW)
            check_hash(f"hash/sweep {i // p}", res, ref)


def run_mcas(seed: int, spec, state, oracle, t: int = TXNS, w: int = TXN_W):
    from repro import atomics
    from repro.txn import mcas as txn_mcas

    rng = np.random.default_rng(seed + 2)
    n, k = spec.n, spec.k
    # Zipf cells, distinct within a transaction, taken in order from one
    # draw eight times as long as needed.
    draw = iter(scatter(zipf_ranks(rng, n, 8 * t * w), n).tolist())
    slot = np.empty((t, w), np.int32)
    for i in range(t):
        row: list[int] = []
        while len(row) < w:
            s = next(draw)
            if s not in row:
                row.append(s)
        slot[i] = row
    expected = oracle.data[slot].copy()
    stale = rng.random(t) < 0.2
    expected[stale] ^= np.uint32(1)
    desired = rng.integers(0, 2 ** 32, (t, w, k), dtype=np.uint32)
    txns = atomics.make_txns(slot, expected, desired, k=k)

    with phase("mcas", nbytes(state)):
        state, res = atomics.mcas(spec, state, txns)
        order = txn_mcas.linearization_order(res)
        require(len(order) == t, "mcas: unresolved transactions")
        data, version, success, witness = txn_mcas.mcas_reference(
            oracle.data, oracle.version, txns, order)
        check_equal("mcas: success", res.success, success)
        check_equal("mcas: witness", res.witness, witness)
        require(np.asarray(res.success).any()
                and not np.asarray(res.success).all(),
                "mcas: mix not exercised")
        touched = np.unique(slot)
        check_equal("mcas: touched values",
                    atomics.logical(spec, state)[touched], data[touched])
        check_equal("mcas: touched versions", state.version[touched],
                    version[touched])


def serving_config():
    """deepseek-7b at its published widths, cut to two layers."""
    from repro.configs import get_config
    return dataclasses.replace(get_config("deepseek-7b"), n_layers=2)


def run_serving(seed: int, cfg=None, prompt: int = PROMPT,
                new_tokens: int = NEW_TOKENS):
    from repro.models.transformer import forward, init_params
    from repro.serving import Request, ServingEngine

    cfg = cfg or serving_config()
    params = jax.jit(init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed + 3)
    prompts = [rng.integers(0, cfg.vocab, prompt).astype(np.int32)
               for _ in range(REQUESTS)]
    page = 16
    pages_per_seq = -(-(prompt + new_tokens) // page)
    streams = {}
    for strategy in SERVE_STRATEGIES:
        with phase(f"serving/{strategy}", nbytes(params)):
            eng = ServingEngine(cfg, params, max_batch=REQUESTS,
                                n_pages=REQUESTS * pages_per_seq,
                                page_size=page,
                                max_pages_per_seq=pages_per_seq,
                                strategy=strategy, seed=seed)
            for rid, toks in enumerate(prompts):
                eng.submit(Request(rid=rid, prompt=toks,
                                   max_new_tokens=new_tokens))
            eng.commit_admissions(eng.admit_compute())
            live = [i for i, s in enumerate(eng.slots) if s.active]
            require(len(live) == REQUESTS, "serving: admitted", live)
            rids = [eng.slots[i].rid for i in live]
            first = [eng.requests[r].out_tokens[0] for r in rids]
            logits = eng.dispatch_decode(live)
            eng.finish_decode(live, logits)
            logits = np.asarray(logits[:, 0].astype(jnp.float32))
            out = eng.run_to_completion()
            require(all(len(v) == new_tokens for v in out.values()),
                    "serving: tokens per request", out)
            streams[strategy] = out

    with phase("serving/reference", nbytes(params)):
        ref_fn = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t})[0])
        worst = 0.0
        for j, rid in enumerate(rids):
            seq = np.concatenate([prompts[rid], [first[j]]])[None]
            ref = np.asarray(ref_fn(params, jnp.asarray(seq, jnp.int32))
                             [0, -1].astype(jnp.float32))
            err = float(np.max(np.abs(logits[j] - ref)))
            bound = LOGIT_ATOL + LOGIT_RTOL * float(np.max(np.abs(ref)))
            require(np.isfinite(logits[j]).all() and err <= bound,
                    "serving: decode logits", rid, err, bound)
            worst = max(worst, err / bound)
        print(json.dumps({"serving_logit_err_over_bound": worst}),
              flush=True)
    a, b = (streams[s] for s in SERVE_STRATEGIES)
    require(a == b, "serving: token streams differ across strategies", a, b)


# ---------------------------------------------------------------------------
# Four chips: the sharded path against one device
# ---------------------------------------------------------------------------

def check_placement(dstate, n_dev: int) -> None:
    """Each shard of every leaf sits on its own device."""
    for leaf in jax.tree.leaves(dstate.local):
        devs = {sh.device for sh in leaf.addressable_shards}
        require(len(devs) == n_dev, "shard placement", leaf.shape, devs)


def permuted(ops, order):
    from repro.core import engine
    return engine.OpBatch(*[jnp.asarray(np.asarray(x)[order]) for x in ops])


def run_sharded(seed: int, n: int = TABLE_N, k: int = TABLE_K,
                p: int = TABLE_P, nb: int = HASH_NB):
    from repro import atomics
    from repro.core import cachehash as ch
    from repro.core import distributed as dsb
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    s = len(devices)
    mesh = make_mesh((s,), ("shard",), devices=devices)
    spec = atomics.AtomicSpec(n, k, "cached_me", p_max=p)
    dspec = dsb.DistSpec(spec, "shard", s, p // s)
    initial = np.asarray(jax.random.bits(jax.random.PRNGKey(seed), (n, k),
                                         jnp.uint32))
    dstate = dsb.init_dist(mesh, dspec, initial)
    check_placement(dstate, s)
    state = atomics.init(spec, initial)
    rng = np.random.default_rng(seed)
    batches = table_batches(rng, n, k, p, initial)[:2]
    for name, ops, _ in batches:
        with phase(f"sharded/table/{name}", nbytes(dstate)):
            dstate, _, dres, ovf = dsb.apply(mesh, dspec, dstate, ops)
            require(not np.asarray(ovf).any(), f"{name}: route overflow")
            order, _ = dsb.linearization_order(dspec, ops)
            require(len(order) == p, f"{name}: claimed order", len(order))
            state, _, res, _, _ = atomics.apply(spec, state,
                                                permuted(ops, order))
            check_equal(f"sharded {name}: values",
                        np.asarray(dres.value)[order], res.value)
            check_equal(f"sharded {name}: success",
                        np.asarray(dres.success)[order], res.success)
            check_equal(f"sharded {name}: table", dsb.logical(dspec, dstate),
                        atomics.logical(spec, state))
            check_equal(f"sharded {name}: versions",
                        dsb.versions(dspec, dstate), state.version)
    check_placement(dstate, s)

    hspec = atomics.HashSpec(nb, HASH_VW, "cached_me", p_max=p, inline=True)
    hdspec = dsb.DistSpec(hspec, "shard", s, p // s)
    hdstate = dsb.init_dist(mesh, hdspec)
    check_placement(hdstate, s)
    hstate = ch.init_hash(hspec)
    keys = hash_keys(rng, p)
    kinds = np.asarray([atomics.FIND, atomics.INSERT, atomics.DELETE],
                       np.int32)
    load = hash_ops(np.full(p, atomics.INSERT, np.int32), keys,
                    rng.integers(0, 2 ** 32, (p, HASH_VW), dtype=np.uint32))
    mix = hash_ops(rng.choice(kinds, p, p=MIX),
                   keys[scatter(zipf_ranks(rng, p, p), p)],
                   rng.integers(0, 2 ** 32, (p, HASH_VW), dtype=np.uint32))
    for name, ops in (("load", load), ("mix", mix)):
        with phase(f"sharded/hash/{name}", nbytes(hdstate)):
            hdstate, dres, ovf = dsb.apply_hash(mesh, hdspec, hdstate, ops)
            require(not np.asarray(ovf).any(),
                    f"hash {name}: route overflow")
            order, _ = dsb.linearization_order(hdspec, ops)
            hstate, res, _ = ch.apply_hash(hspec, hstate,
                                           permuted(ops, order))
            check_equal(f"sharded hash {name}: found",
                        np.asarray(dres.found)[order], res.found)
            check_equal(f"sharded hash {name}: value",
                        np.asarray(dres.value)[order], res.value)
    check_placement(hdstate, s)


# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, found "
                         f"{len(devices)}")
    print(json.dumps({"phase": "device", "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "count": len(devices)}), flush=True)
    return dev, len(devices)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev, count = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    monitoring.register_event_duration_secs_listener(_on_duration)

    if args.chips == 4:
        run_sharded(args.seed)
    else:
        for strategy in STRATEGIES:
            spec, state, oracle = run_table(args.seed, strategy)
        run_hash(args.seed)
        run_mcas(args.seed, spec, state, oracle)
        del state, oracle
        run_serving(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
