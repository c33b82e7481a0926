"""Chip benchmark of the simulator's sweep path (see PERF.md)."""
