"""Benchmark of protected serving and training on the chip (see BENCHMARK.json)."""
