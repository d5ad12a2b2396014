"""Benchmark scripts package (so bench.py and the scripts can share
benchmarks/_timing.py, the fetch-sync slope-timing utility)."""
