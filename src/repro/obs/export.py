"""The counter sink: one JSON object per line with the stable schema

    {"metric": "<name from DESIGN.md §10>", "value": <int|float>}

so downstream tooling can stream-parse it without knowing the full set of
metric names in advance.
"""

from __future__ import annotations

import json

from repro.obs.telemetry import derived, snapshot


def write_metrics_jsonl(path: str, extra: dict | None = None) -> None:
    """Dump the global counter snapshot (+ derived rates, + any `extra`
    host counters such as `Recorder.metrics()`) as one metric per line."""
    snap = snapshot()
    snap.update(derived(snap))
    if extra:
        snap.update(extra)
    with open(path, "w") as f:
        for name in sorted(snap):
            f.write(json.dumps({"metric": name, "value": snap[name]}))
            f.write("\n")
