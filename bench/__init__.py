"""Chip benchmark of the quantized serving stack (see BENCHMARK.json)."""
