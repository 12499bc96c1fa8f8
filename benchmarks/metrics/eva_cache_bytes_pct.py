"""The pages' share of the bytes a decode step has to move
(costs_evabyte.decode_step_bytes over the window's log of pages held): how
much of a step the summaries and the open windows are, by the count
alone."""
from benchmarks.harness import costs_evabyte, readers


def read(record):
    moved = costs_evabyte.window_step_bytes(record, *readers.window(record))
    return 100.0 * moved["cache"] / moved["total"] if moved else None
