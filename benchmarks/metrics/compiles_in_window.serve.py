"""Compiles the replica's process counted inside the window; must be 0."""
from benchmarks.harness import readers


def read(record):
    return readers.compiles_in_window(record)
