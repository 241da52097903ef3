"""Chip benchmark of the AVERY cloud serving path (see PERF.md)."""
