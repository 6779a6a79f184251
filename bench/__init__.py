"""The on-chip benchmark of the PPF library: one command, cells named in
``BENCHMARK.json``, configurations, traffic mixes and per-layer metric
readers each in a file of their own (see ``bench/run.py``)."""
