"""Least time the chip could take to move one decode step's bytes (the
weights that multiply, once, with the sampled prediction head's columns;
every decoding row's pages whole, summaries and open window alike, from the
replica's log of pages held: costs_evabyte.decode_step_bytes, over the
published HBM bandwidth), over the decode_step program's device time a
call. Bound by bytes."""
from benchmarks.harness import costs_evabyte, readers


def read(record):
    trace = readers.trace_of(record)
    program = readers.program(record, "decode_step")
    if not trace or not program or not program["calls"]:
        return None
    moved = costs_evabyte.window_step_bytes(
        record, trace["host_began"], trace["host_ended"])
    if moved is None:
        return None
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
