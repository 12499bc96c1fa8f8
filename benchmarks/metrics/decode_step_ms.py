"""Host wall time of a decode tick (accel plane's step summary, StepTimer
around the tick), mean over the window."""
from benchmarks.harness import readers


def read(record):
    a = readers.step_row(record["opened"], "decode")
    b = readers.step_row(record["closed"], "decode")
    steps = b["steps"] - a["steps"]
    return (b["wall_s"] - a["wall_s"]) / steps * 1e3 if steps else None
