"""The benchmark of the served window path (see PERF.md)."""
