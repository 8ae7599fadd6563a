"""The benchmark's harness: everything a run does that is not the scheduler.

Data drives it: a cell in ``BENCHMARK.json`` names a configuration and a
traffic mix, which are files under ``benchmark/configs`` and
``benchmark/traffic``; a per-layer metric is a file under
``benchmark/layer_metrics``.  Nothing here names a cell, a mix or a metric.
"""
