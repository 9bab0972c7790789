"""The benchmark: one command, `python3 bench/run.py`, driven by BENCHMARK.json."""
