"""A cell of another kind of system, on four chips, comes into the
benchmark by new files alone.

A copy of `bench/` and `BENCHMARK.json` gets a probe: a table sharded over
four devices through `atomics.dist.apply` (a system module), its
configuration, a reader of a device scope the sharded round records, and
new `BENCHMARK.json` entries for them and for one `chips: 4` cell.  No
file that was there changes.  The copy's own generic checks
(`test_bench_run.py`, run by pytest in a child process) then pass for
that cell, on four CPU devices.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

from bench import run

from .cells import case_names

CELL = "probe-dist.ycsb-a-zipf"
READER = "probe_sort_ms"

SYSTEM = '''"""Probe: a table of big atomics sharded over the cell's
devices, one shard a device, driven through `atomics.dist.apply`."""

from typing import NamedTuple

import numpy as np

from bench import gen
from bench.systems import table
from repro import atomics
from repro.launch.mesh import make_mesh

CODES, WRITES, Control = table.CODES, table.WRITES, table.Control
TINY = {}


class Spec(NamedTuple):
    mesh: object
    dspec: object


def entry(spec, state, ops):
    return atomics.dist.apply(spec.mesh, spec.dspec, state, ops)


def change_record(state, record, field):
    local = state.local
    shard, i = divmod(record, local.version.shape[1])
    if field == "data":
        local = local._replace(data=local.data.at[shard, i, 0].add(1))
    else:
        local = local._replace(version=local.version.at[shard, i].set(2))
    return state._replace(local=local)


class Cell(table.Cell):
    def _build(self):
        d = len(self.devices)
        mesh = make_mesh((d,), ("shard",), devices=self.devices)
        self.dist = Spec(mesh, atomics.DistSpec(self.spec, "shard", d,
                                                self.spec.p_max // d))
        start = gen.cell_words(self.seed, np.arange(self.spec.n), self.k)
        return atomics.dist.init_dist(mesh, self.dist.dspec, start)

    def call(self, b):
        self.state, _, res, _ = entry(self.dist, self.state, self.ops[b])
        return res.value, res.success

    def readback(self, history):
        cells = self.touched(history)
        vals = np.asarray(atomics.dist.logical(self.dist.dspec, self.state))
        vers = np.asarray(atomics.dist.versions(self.dist.dspec, self.state))
        start = gen.cell_words(self.seed, np.arange(self.spec.n), self.k)
        moved = np.any(vals != start, axis=1) | (vers != 0)
        moved[cells] = False
        return cells, vals[cells], vers[cells], int(moved.sum())
'''

READER_FILE = '''"""Probe: device time of the scope `engine.sort` of the
sharded round, per batch, mean over the chips."""

from bench.metrics import scope_ms_per_batch


def read(run):
    return scope_ms_per_batch(run, "engine.sort")
'''

CONFIG = {"system": "probe_dist", "strategy": "cached_me", "n_cells": 4096,
          "k_words": 8, "lanes": 64}
ENTRIES = {
    "configs": {"name": "probe-dist", "source": "arXiv:2501.07503",
                "file": "bench/configs/probe-dist.json", "reduced": [],
                "why": "a sharded table of big atomics"},
    "workloads": {"name": CELL, "config": "probe-dist",
                  "traffic": "ycsb-a-zipf", "chips": 4,
                  "why": "YCSB-A over four shards: route, apply, return"},
    "per_layer": {"name": READER, "unit": "ms", "better": "lower",
                  "source": "device_trace", "layer": "probe",
                  "moves": "ops_per_s", "workloads": [CELL]},
}
NEW_FILES = {"bench/systems/probe_dist.py": SYSTEM,
             f"bench/metrics/{READER}.py": READER_FILE,
             "bench/configs/probe-dist.json": json.dumps(CONFIG)}
TESTS = {"sound": "test_sound_run_is_correct",
         "traced": "test_traced_run_reads_its_metrics",
         "fault": "test_planted_fault_is_not_correct",
         "untouched": "test_untouched_cell_changed_is_not_correct",
         "control": "test_control_is_not_correct"}


def _digests(root) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy_results(tmp_path_factory):
    """{test id: passed} of the copy's generic checks of the probe cell."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(run.HERE, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    before = _digests(root)
    for rel, text in NEW_FILES.items():
        (root / rel).write_text(text)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entry in ENTRIES.items():
        bench[key].append(entry)
    # The probe cell's only per-layer metric is its reader.
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])] == [READER]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    after = _digests(root)
    edited = {f for f in before if f != "BENCHMARK.json"
              and after[f] != before[f]}
    assert not edited and set(after) - set(before) == set(NEW_FILES)
    os.symlink(run.SRC, root / "src")

    xml = root / "probe.xml"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "XLA_FLAGS"))}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), run.SRC]))
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "bench/tests/test_bench_run.py", "-k", "probe or resolves_by_name",
         f"--junitxml={xml}"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    results = {}
    for case in ET.parse(xml).iter("testcase"):
        results[case.get("name")] = not any(
            c.tag in ("failure", "error", "skipped") for c in case)
    return results, out.stdout[-4000:]


def _test_id(case: str) -> str:
    kind, _, arg = case.partition(":")
    return f"{TESTS[kind]}[{CELL}{'-' + arg if arg else ''}]"


def test_the_probe_resolves_by_name(copy_results):
    results, log = copy_results
    assert results.get("test_every_cell_resolves_by_name"), log


@pytest.mark.parametrize("case", case_names(writes=True, records=True))
def test_the_probe_passes_a_generic_check(case, copy_results):
    """Each generic check of the probe cell passes in the copy; its one
    per-layer metric is the probe's reader, which the traced check finds
    present."""
    results, log = copy_results
    assert results.get(_test_id(case)), log
    assert len([t for t in results if CELL in t]) == len(
        case_names(writes=True, records=True))
