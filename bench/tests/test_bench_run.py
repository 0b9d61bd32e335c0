"""The harness end to end on the CPU: no result without a TPU, the plan
of every cell, and `correct` against planted faults and the controls, each
cell on as many CPU devices as it asks for chips (`cells.py`)."""

import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run

from .cells import benchmark, cases, cells, outcome, tiny_plan

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
        assert all(hasattr(mod, name) for name in
                   ("CODES", "WRITES", "TINY", "entry", "Cell", "Control"))
        assert set(plan["traffic"]["mix"]) <= set(mod.CODES)


CASES = [(c, case) for c in cells() for case in cases(c)]


def _of(kind: str) -> list:
    return [(c, case.partition(":")[2]) for c, case in CASES
            if case.startswith(kind + ":")]


@pytest.mark.parametrize("cell", cells())
def test_sound_run_is_correct(cell):
    out = outcome(cell, "sound")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["window_compiles"] == 0
    assert list(out)[-1] == "checks"
    plan = tiny_plan(cell)
    assert set(out["metrics"]) == {m["name"] for m in plan["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == plan["chips"]


@pytest.mark.parametrize("cell", cells())
def test_traced_run_reads_its_metrics(cell):
    """A traced run is as correct, and reads per-layer metrics of its own
    cell only, at least one of them (on the CPU a reader of the chip's
    kernels finds nothing to read)."""
    out = outcome(cell, "traced")
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0
    names = {m["name"] for m in tiny_plan(cell)["per_layer"]}
    assert out["metrics"] and set(out["metrics"]) <= names
    assert all(np.isfinite(m["value"]) and m["value"] >= 0
               for m in out["metrics"].values())
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,fault", _of("fault"))
def test_planted_fault_is_not_correct(cell, fault):
    out = outcome(cell, f"fault:{fault}")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell,field", _of("untouched"))
def test_untouched_cell_changed_is_not_correct(cell, field):
    """A step that also changes a record no batch names is caught by the
    whole-table count (the check named `untouched...`), and by nothing
    else."""
    out = outcome(cell, f"untouched:{field}")
    assert not out["correct"]
    checks = {k: c["value"] for k, c in out["checks"].items()}
    assert {k: v for k, v in checks.items() if v} == {
        k: 1 for k in checks if k.startswith("untouched")}, checks


@pytest.mark.parametrize("cell,weaken", _of("control"))
def test_control_is_not_correct(cell, weaken):
    out = outcome(cell, f"control:{weaken}")
    assert not out["correct"], out["checks"]
    assert out["checks"]["op_mismatches"]["value"] > 0
