"""Least time the chip could take to move one decode tick's bytes (the
weights that multiply, once; the recurrent state of each decoding row, read
and written; whole K/V pages of each decoding row's context: from the tick
log by costs_hybrid.decode_tick_bytes, over the published HBM bandwidth),
over the decode_step program's device time a call. Bound by bytes."""
from benchmarks.harness import costs_hybrid, readers


def read(record):
    trace = readers.trace_of(record)
    program = readers.program(record, "decode_step")
    if not trace or not program or not program["calls"]:
        return None
    moved = costs_hybrid.window_tick_bytes(
        record, trace["host_began"], trace["host_ended"])
    if moved is None:
        return None
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
