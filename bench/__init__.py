"""The chip benchmark of the learned-index service (see PERF.md)."""
