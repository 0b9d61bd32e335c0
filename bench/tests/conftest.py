"""Tiny plans for the benchmark's CPU tests: every cell of BENCHMARK.json
with its configuration cut to a few thousand cells and 64 lanes."""

from __future__ import annotations

import json
import os

import pytest

from bench import run

TINY = {"table": {"n_cells": 4096, "lanes": 64}}


def benchmark() -> dict:
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cells() -> list:
    return [w["name"] for w in benchmark()["workloads"]]


def tiny_plan(cell: str) -> dict:
    plan = run.plan_for(benchmark(), cell)
    plan["config"].update(TINY[plan["config"]["system"]])
    plan["traffic"]["pool_batches"] = min(plan["traffic"]["pool_batches"], 8)
    return plan


@pytest.fixture
def cpu():
    import jax
    return jax.devices("cpu")[:1]
