"""On-chip benchmark of the encrypted search service (see BENCHMARK.json)."""
