"""The serving benchmark: one cell of BENCHMARK.json per run of run.py."""
