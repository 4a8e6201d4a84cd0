"""The on-chip benchmark (see PERF.md). Entry point: benchmark/run.py."""
