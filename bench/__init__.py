"""The chip benchmark of the replica-exchange driver (see PERF.md)."""
