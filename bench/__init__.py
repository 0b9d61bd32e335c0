"""The on-chip benchmark of the big-atomics system (see BENCHMARK.json).

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>` runs one cell.  Each configuration is
`configs/<name>.json`, each traffic mix `traffic/<name>.json`, each
per-layer metric a reader `metrics/<name>.py`, and each kind of system
under test an adapter `systems/<system>.py`: the harness finds all of
them by name, so a new cell needs new files and no edit.
"""
