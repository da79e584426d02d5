"""The benchmark: BENCHMARK.json's cells, run one at a time on the chip.

Everything a number depends on lives under this directory (and its
tests under ``tests/benchmark/``): traffic generation, the reduction
from traces and counters to metrics, the table of peaks, the functions
that compute a kernel's operations and bytes, each configuration's plain
reference, and the comparison that decides ``correct``.  From the
program it takes only the system under test, its counters and the names
of its kernels.  See ``run.py`` and PERF.md.
"""
