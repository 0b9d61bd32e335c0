"""The benchmark's generic CPU checks of one cell, at a tiny size and on as
many CPU devices as the cell asks for chips.

Each check is a case of one run of the harness (`run.run_cell` or
`control.run_control`), named:

  sound                  the program as it is, untraced;
  traced                 the same with `--trace 1`;
  fault:<name>           the system's `entry` wrapped by a planted fault
                         (`unchanged` only where the traffic writes);
  untouched:<field>      a record no batch names changed by the system's
                         `change_record` (where the system defines it);
  control:<weaken>       the weakened reference in the program's place
                         (`snapshot` only where the traffic writes).

A one-chip cell's cases run in the test's own process on one CPU device.
A cell on more chips runs all of its cases in one child process whose CPU
backend has that many devices (`--xla_force_host_platform_device_count`),
so the test process keeps its own device count:

  python -m bench.tests.cells <cell>

prints {case: result line's object} as its last line.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import subprocess
import sys

import numpy as np

from bench import control, run

SEEDS = {"sound": 2 ** 33 + 5, "traced": 2 ** 33 + 5, "fault": 2 ** 33 + 6,
         "control": 2 ** 33 + 7, "untouched": 2 ** 33 + 8}
FIELDS = ("data", "version")
WINDOW_S = 0.2


def benchmark() -> dict:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> list:
    return [w["name"] for w in benchmark()["workloads"]]


def tiny_plan(cell: str) -> dict:
    plan = run.plan_for(benchmark(), cell)
    plan["config"].update(run.system(plan["config"]["system"]).TINY)
    plan["traffic"]["pool_batches"] = min(plan["traffic"]["pool_batches"], 8)
    return plan


def _writes(plan: dict) -> bool:
    mod = run.system(plan["config"]["system"])
    return any(plan["traffic"]["mix"].get(op, 0) > 0 for op in mod.WRITES)


def faults(idle: int) -> dict:
    """Faults planted under the timed entry point, for a system whose idle
    lane has the code `idle`: name -> wrapper."""

    def unchanged(entry):
        def f(spec, state, ops):
            return (state, *entry(spec, state, ops)[1:])
        return f

    def half_left_out(entry):
        def f(spec, state, ops):
            kind = np.asarray(ops.kind).copy()
            kind[len(kind) // 2:] = idle
            return entry(spec, state, ops._replace(kind=kind))
        return f

    def answer_altered(entry):
        def f(spec, state, ops):
            out = list(entry(spec, state, ops))
            i = next(i for i, x in enumerate(out) if hasattr(x, "success"))
            value = out[i].value.at[0, 0].set(out[i].value[0, 0] ^ 1)
            out[i] = out[i]._replace(value=value)
            return tuple(out)
        return f

    return {"unchanged": unchanged, "half_left_out": half_left_out,
            "answer_altered": answer_altered}


def case_names(writes: bool, records: bool) -> list:
    """The cases of a cell whose traffic writes or not, of a system that
    has records no batch names (`change_record`) or not."""
    out = ["sound", "traced"]
    out += [f"fault:{f}" for f in faults(0) if writes or f != "unchanged"]
    if records:
        out += [f"untouched:{f}" for f in FIELDS]
    out += [f"control:{w}" for w in control.WEAKEN
            if writes or w != "snapshot"]
    return out


def cases(cell: str) -> list:
    plan = run.plan_for(benchmark(), cell)
    mod = run.system(plan["config"]["system"])
    return case_names(_writes(plan), hasattr(mod, "change_record"))


def _change_one_more(mod, record: int, field: str):
    entry = mod.entry

    def f(spec, state, ops):
        state, *rest = entry(spec, state, ops)
        return (mod.change_record(state, record, field), *rest)
    return f


def run_case(plan: dict, case: str, devices) -> dict:
    """One case of `cases`, on `devices`."""
    kind, _, arg = case.partition(":")
    seed = SEEDS[kind]
    if kind == "control":
        return control.run_control(plan, seed, WINDOW_S, arg, devices)
    if kind in ("sound", "traced"):
        return run.run_cell(plan, seed, WINDOW_S, kind == "traced", devices)
    mod = run.system(plan["config"]["system"])
    if kind == "fault":
        planted = faults(mod.CODES["IDLE"])[arg](mod.entry)
    else:
        probe = mod.Cell(plan["config"], plan["traffic"], seed, devices)
        named = set(probe.touched(range(len(probe.ops))).tolist())
        probe.free()
        victim = next(i for i in itertools.count() if i not in named)
        planted = _change_one_more(mod, victim, arg)
    entry, mod.entry = mod.entry, planted
    try:
        return run.run_cell(plan, seed, WINDOW_S, False, devices)
    finally:
        mod.entry = entry


@functools.cache
def _child(cell: str, chips: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([run.ROOT, run.SRC]),
           "XLA_FLAGS": f"{os.environ.get('XLA_FLAGS', '')} "
                        f"--xla_force_host_platform_device_count={chips}"}
    out = subprocess.run([sys.executable, "-m", "bench.tests.cells", cell],
                         cwd=run.ROOT, env=env, capture_output=True,
                         text=True, timeout=900)
    if out.returncode:
        raise RuntimeError(f"cells {cell} exited {out.returncode}:\n"
                           f"{out.stderr[-4000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def outcome(cell: str, case: str) -> dict:
    """The result line's object of one case of a cell."""
    plan = tiny_plan(cell)
    if plan["chips"] > 1:
        return _child(cell, plan["chips"])[case]
    import jax
    return run_case(plan, case, jax.devices("cpu")[:1])


def main(argv=None) -> None:
    import jax
    cell, = sys.argv[1:] if argv is None else argv
    plan = tiny_plan(cell)
    devices = jax.devices("cpu")
    if len(devices) < plan["chips"]:
        raise SystemExit(f"cells: {cell} needs {plan['chips']} devices, "
                         f"found {len(devices)}")
    results = {case: run_case(tiny_plan(cell), case,
                              devices[:plan["chips"]])
               for case in cases(cell)}
    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
