"""Shared benchmark machinery: timing, result I/O, table printing.

Wall-clock numbers here are CPU/XLA throughput — they reproduce the paper's
*relative* strategy ordering and contention curves (Figs 1-5).  The absolute
TPU numbers come from the modeled Traffic terms (bytes, dependency depth,
RMWs) that every bench also records; EXPERIMENTS.md reads both.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def save_results(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def run_cpu_child(script: str, *, timeout: int):
    """Run `script` in a child Python on the CPU backend and return the
    JSON it prints on its `JSON:` line.

    Children simulate several devices on the host (`XLA_FLAGS` in the
    script).  They must not reach for the accelerator, which the parent
    process holds once it has touched JAX, so their environment pins
    `JAX_PLATFORMS=cpu`; callers label what comes back `backend: cpu`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("JSON:")]
    assert line, r.stdout + r.stderr[-2000:]
    return json.loads(line[0][5:])


def time_op(fn, *args, reps: int = 5, warmup: int = 2):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


def print_table(title: str, rows: list[dict], cols: list[str]):
    print(f"\n== {title} ==")
    widths = {c: max(len(c), *(len(_fmt(r.get(c))) for r in rows))
              for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(_fmt(r.get(c)).ljust(widths[c]) for c in cols))


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e4 or abs(v) < 1e-3:
            return f"{v:.3e}"
        return f"{v:.4g}"
    return str(v)
