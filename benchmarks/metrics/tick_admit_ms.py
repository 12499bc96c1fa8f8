"""Phases `reap` + `admit` + `grow` per tick: cancellations, draining the
queue, radix match, page allocation and eviction, the gather_pages /
dense_zero_caches dispatch, lazy page growth and preemption."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "reap", "admit", "grow")
