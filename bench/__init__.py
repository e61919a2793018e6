"""On-chip benchmark of the retrieval service (see BENCHMARK.json)."""
