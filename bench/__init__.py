"""Benchmark harness of this repository (see BENCHMARK.json)."""
