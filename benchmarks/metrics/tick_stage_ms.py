"""Phases `stage` + `dispatch` per tick: building the six numpy arrays of
the decode call, uploading them, the rng split, and the call into the
jitted decode program until it returns."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "stage", "dispatch")
