"""The harness end to end on the CPU: no result without a TPU, the plan
of every cell, and `correct` against planted faults and the controls."""

import os
import re
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control, run
from bench.systems import table as table_sys

from .conftest import benchmark, cells, tiny_plan

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _run_script(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    out = _run_script(run.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_cell_resolves_by_name():
    bench = benchmark()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert callable(run.reader(m["name"]))
    for cell in cells():
        assert NAME.match(cell)
        plan = run.plan_for(bench, cell)
        e2e = {m["name"] for m in plan["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and plan["per_layer"]
        assert all(m["moves"] in e2e for m in plan["per_layer"])
        mod = run.system(plan["config"]["system"])
        assert set(plan["traffic"]["mix"]) <= set(mod.CODES)


@pytest.mark.parametrize("cell", cells())
def test_sound_run_is_correct(cell, cpu):
    out = run.run_cell(tiny_plan(cell), 2 ** 33 + 5, 0.2, False, cpu)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert list(out)[-1] == "checks"
    plan = tiny_plan(cell)
    assert set(out["metrics"]) == {m["name"] for m in plan["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())


def _faults(module):
    """Faults planted under the timed entry point: name -> wrapper."""
    idle = module.CODES["IDLE"]

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


FAULT_CASES = [(c, f) for c in cells() for f in
               ("unchanged", "half_left_out", "answer_altered")
               if not (f == "unchanged" and "ycsb-c" in c)]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_planted_fault_is_not_correct(cell, fault, cpu, monkeypatch):
    plan = tiny_plan(cell)
    monkeypatch.setattr(table_sys, "entry",
                        _faults(table_sys)[fault](table_sys.entry))
    out = run.run_cell(plan, 2 ** 33 + 6, 0.2, False, cpu)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("field", ["data", "version"])
def test_untouched_cell_changed_is_not_correct(cell, field, cpu,
                                               monkeypatch):
    """A step that also changes a cell no batch names is caught by the
    whole-table count, and by nothing else."""
    plan, seed = tiny_plan(cell), 2 ** 33 + 8
    probe = table_sys.Cell(plan["config"], plan["traffic"], seed)
    named = set(probe.touched(range(len(probe.ops))).tolist())
    victim = min(set(range(probe.spec.n)) - named)
    entry = table_sys.entry

    def changes_one_more(spec, state, ops):
        state, *rest = entry(spec, state, ops)
        if field == "data":
            state = state._replace(
                data=state.data.at[victim, 0].add(1))
        else:
            state = state._replace(
                version=state.version.at[victim].set(2))
        return (state, *rest)

    monkeypatch.setattr(table_sys, "entry", changes_one_more)
    out = run.run_cell(plan, seed, 0.2, False, cpu)
    assert not out["correct"]
    checks = {k: c["value"] for k, c in out["checks"].items()}
    assert checks == {"op_mismatches": 0, "cell_mismatches": 0,
                      "untouched_mismatches": 1}, checks


CONTROL_CASES = [(c, w) for c in cells() for w in ("half_words", "snapshot")
                 if not (w == "snapshot" and "ycsb-c" in c)]


@pytest.mark.parametrize("cell,weaken", CONTROL_CASES)
def test_control_is_not_correct(cell, weaken, cpu):
    out = control.run_control(tiny_plan(cell), 2 ** 33 + 7, 0.2, weaken, cpu)
    assert not out["correct"], out["checks"]
    assert out["checks"]["op_mismatches"]["value"] > 0
