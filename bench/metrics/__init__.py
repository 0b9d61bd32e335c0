"""Metric readers, one file per metric of BENCHMARK.json, named as the
metric.  Each defines `read(run)`, which returns the metric's value, or
None where the run holds nothing for it to read."""
