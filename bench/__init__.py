"""Chip benchmark of the CapsNet serving and training paths (see PERF.md)."""
