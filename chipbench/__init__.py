"""On-chip benchmark of the async HeLoCo trainer (see PERF.md)."""
