"""The chip benchmark: see `BENCHMARK.json` and `bench/run.py`."""
