"""The benchmark: one cell of BENCHMARK.json, run once, one result line.

Everything that decides a number lives here and not in the program:
traffic generation, the reduction from traces and counters to metrics,
the table of peaks, the FLOPs arithmetic, the plain reference and the
comparison that decides ``correct``. See README.md.
"""
