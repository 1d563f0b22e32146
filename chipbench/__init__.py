"""On-chip benchmark harness (see ``run.py`` and ``BENCHMARK.json``)."""
