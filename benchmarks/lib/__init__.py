"""The benchmark's yardstick: what later PRs may not move.

Peaks, operation and byte counts, the trace reduction, percentile
arithmetic, the compile counter and the plain references live here.
From the program the benchmark takes only the system under test.
"""
