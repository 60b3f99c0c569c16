"""Helpers for whoever measures: not run by `BENCHMARK.json`'s command."""
