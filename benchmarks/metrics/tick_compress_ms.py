"""Phase `compress` per tick: the `compress_window` dispatches and the
page hand-back of the rows whose window a decode step filled."""
from benchmarks.harness import tickphases


def read(record):
    if "window_closes_decode" not in record["closed"]["stats"]:
        return None
    return tickphases.phase_ms(record, "compress")
