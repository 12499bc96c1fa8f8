"""memory_stats() of the fullest chip after the window, in the process that
holds the chip: peak_bytes_in_use (arrays) + peak_bytes_reserved (program
temporaries)."""
from benchmarks.harness import readers


def read(record):
    return readers.hbm_peak_gib(record)
