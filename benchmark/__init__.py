"""The benchmark of tmlibrary_tpu: one command runs one cell once
(``python3 benchmark/run.py``); see ``BENCHMARK.json`` and ``PERF.md``."""
