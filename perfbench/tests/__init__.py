"""CPU tests of the benchmark harness, its reduction and its checks."""
