"""The on-chip benchmark of the video monitoring query engine (see
``bench/run.py`` and PERF.md)."""
