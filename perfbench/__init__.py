"""SkewRoute router benchmark: see harness.py and BENCHMARK.json at the repository root."""
