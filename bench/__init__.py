"""On-chip benchmark of the served path: cells (configuration x traffic
mix) named in ``BENCHMARK.json``, run by ``bench/run.py``."""
