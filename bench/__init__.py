"""On-chip benchmark of the guided-generation system (see BENCHMARK.json)."""
