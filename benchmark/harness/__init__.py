"""Shared machinery of the benchmark (nothing here names a cell)."""
