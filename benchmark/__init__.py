"""The on-chip benchmark: everything `BENCHMARK.json`'s command runs."""
