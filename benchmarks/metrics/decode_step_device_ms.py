"""Device time of the decode program per tick, from the trace."""
from benchmarks.harness import readers


def read(record):
    row = readers.program(record, "decode_step")
    return row["total_s"] / row["calls"] * 1e3 if row else None
