"""Phases `emit` + `gauges` per tick: the per-row token loop with its
callbacks (call_soon_threadsafe into the replica's loop), finishes and page
release, then the per-tick metric observes and gauges."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "emit", "gauges")
