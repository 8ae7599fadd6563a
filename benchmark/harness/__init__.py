"""The benchmark's harness: what every cell's run shares.

Data drives it: a cell in ``BENCHMARK.json`` names a configuration and a
traffic mix, which are files under ``benchmark/configs`` and
``benchmark/traffic``; the traffic file names the cell's generator
(``benchmark/generators/<name>.py``), the configuration its reference
(``benchmark/reference/<name>.py``) and the scheduler's settings; a
per-layer metric is a file under ``benchmark/layer_metrics``.  Nothing here
names a cell, a mix, a generator, a reference or a metric.
"""
