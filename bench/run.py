"""Run one cell of the benchmark on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's `workloads`.  Its configuration
file, its traffic mix (`bench/traffic/<mix>.json`), its kind of system
(`bench/systems/<system>.py`, named by the configuration) and its metrics'
readers (`bench/metrics/<metric>.py`) are found by name.

  set-up   the system's state on the device from the seed, the window's
           batches drawn from the seed, the traffic's warm-up batches run
           (they compile); timed from process start as `setup_s`;
  window   closed loop: one batch of `lanes` ops at a time through the
           system's entry point, each batch's results on the host before
           the next is submitted, until `--seconds` have passed and the
           batch in flight has finished; with `--trace 1` under the
           profiler, with the harness's own host spans;
  check    after the window: the device's peak memory is read, the
           program's state read back and freed, and every batch's results
           and the state compared with the plain reference.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer ones), `device`, with `--trace 1` `breakdown`,
then `window_compiles` and, last, `checks`: each number compared with its
limit.  The checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")
# Run as a script, this directory heads sys.path; its modules are meant to
# be imported as `bench.<name>` only.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def process_age_s() -> float:
    """Seconds since this process started (to 10 ms), from /proc."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


# ---------------------------------------------------------------------------
# The plan of one cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether the cell reports the metric: the cells its `workloads` names,
    or, for a per-layer metric without the key, every cell that reports
    the end-to-end metric it `moves`."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def plan_for(bench: dict, workload: str) -> dict:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, workload, names)]
    return {"cell": workload, "chips": cell["chips"], "config": config,
            "traffic": traffic, "end_to_end": e2e, "per_layer": layer}


def reader(name: str):
    """`read` of bench/metrics/<name>.py."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def system(name: str):
    return importlib.import_module(f"bench.systems.{name}")


def require_devices(chips: int):
    """The first `chips` TPU devices; exits with no result otherwise."""
    import jax
    from bench.peaks import peaks_for
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(f"bench: needs {chips} chips, found {len(devices)}")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """What a metric's reader may read."""

    plan: dict
    cell: object                       # the system's Cell
    setup_s: float
    window_s: float
    batches: list                      # window: batch index in the pool
    ops: list                          # window: ops per batch
    dispatch_s: list                   # window: entry call until it returns
    latency_s: list                    # window: entry call until results
    results: list                      # window: results on the host
    counters: dict = field(default_factory=dict)  # name -> per batch
    trace: object = None               # bench.devtrace.Summary with --trace 1
    peaks: dict = field(default_factory=dict)


class _Compiles:
    """Counts JAX compile events while `on`."""

    def __init__(self):
        self.on, self.count = False, 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, secs, **_):
        if self.on and name.startswith("/jax/core/compile/"):
            self.count += 1


def run_cell(plan: dict, seed: int, seconds: float, trace: bool, devices,
             make_cell=None) -> dict:
    """Set up, run the window, check; returns the result line's object."""
    import jax
    import numpy as np
    from bench import peaks as peak_table
    from bench import devtrace as tr

    compiles = _Compiles()
    mod = system(plan["config"]["system"])
    cell = (make_cell or mod.Cell)(plan["config"], plan["traffic"], seed,
                                   devices)
    history = list(cell.setup_history)
    results = list(cell.setup_results)
    pool = range(cell.window_first, len(cell.ops))
    for j in range(int(plan["traffic"].get("warmup_batches", 0))):
        b = pool[j % len(pool)]
        results.append(jax.device_get(cell.call(b)))
        history.append(b)

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        tr.start(log_dir)
    span = (jax.profiler.TraceAnnotation if trace
            else lambda _: contextlib.nullcontext())
    batches, ops, dispatch, latency, window_res = [], [], [], [], []
    counters: dict = {}
    failed = 0
    j = int(plan["traffic"].get("warmup_batches", 0))
    # What set-up left behind is not the window's to collect.
    gc.collect()
    gc.freeze()
    compiles.on = True
    setup_s = process_age_s()
    t0 = time.perf_counter()
    while True:
        b = pool[j % len(pool)]
        with span(tr.SPAN_DISPATCH):
            ts = time.perf_counter()
            pending = cell.call(b)
            td = time.perf_counter()
        with span(tr.SPAN_FETCH):
            host = jax.device_get(pending)
            te = time.perf_counter()
        batches.append(b)
        ops.append(cell.lanes(b))
        dispatch.append(td - ts)
        latency.append(te - ts)
        window_res.append(host)
        failed += cell.failed(host)
        for name, v in cell.counters(host).items():
            counters.setdefault(name, []).append(v)
        j += 1
        if te - t0 >= seconds:
            break
    window_s = te - t0
    compiles.on = False
    gc.unfreeze()
    if trace:
        tr.stop()

    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    final = cell.readback(history + batches)
    cell.free()
    results = jax.device_get(results) + window_res
    history += batches
    checks = cell.check(history, results, final)
    correct = all(v <= limit for v, limit in checks.values())

    kind = devices[0].device_kind
    run = Run(plan=plan, cell=cell, setup_s=setup_s,
              window_s=window_s, batches=batches, ops=ops,
              dispatch_s=dispatch, latency_s=latency, results=window_res,
              counters={k: np.asarray(v) for k, v in counters.items()},
              peaks=peak_table.PEAKS.get(kind, {}))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max((p for p in peak if p is not None),
                                       default=None)}
    out = {"correct": correct, "attempted": int(sum(ops)),
           "failed": int(failed)}
    if trace:
        run.trace = tr.reduce(tr.load(log_dir), len(devices))
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in plan["per_layer"] if trace else plan["end_to_end"]:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["window_compiles"] = compiles.count
    out["checks"] = {name: {"value": v, "limit": limit}
                     for name, (v, limit) in checks.items()}
    return out


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_paths() -> None:
    """The program under test is the checkout's `src/`; without it there
    is nothing to measure."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"bench: no program under {SRC}")
    for p in (SRC, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def start_jax(chips: int):
    devices = require_devices(chips)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices


def main(argv=None) -> None:
    args = parse(argv)
    setup_paths()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        plan = plan_for(json.load(f), args.workload)
    devices = start_jax(plan["chips"])
    print_result(run_cell(plan, args.seed, args.seconds, bool(args.trace),
                          devices))


if __name__ == "__main__":
    main()
