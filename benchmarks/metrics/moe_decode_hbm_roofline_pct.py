"""Least time the chip could take to move one decode step's bytes (the
weights that multiply, once, with only the held experts a step HIT counted,
from the engine's expert counters; the recurrent state of each decoding
row, read and written; whole K/V pages of each decoding row's context in
the one layer that attends: costs_nemotron_h.decode_step_bytes over the
tick log, over the published HBM bandwidth), over the decode_step
program's device time a call. Bound by bytes."""
from benchmarks.harness import costs_nemotron_h, readers
from benchmarks.harness import serve_cell_nemotron_h as cell


def read(record):
    trace = readers.trace_of(record)
    program = readers.program(record, "decode_step")
    window = cell.expert_window(record)
    if not trace or not program or not program["calls"] or window is None:
        return None
    ticks = [t for t in record["report"]["ticks"]
             if trace["host_began"] <= t[0] < trace["host_ended"] and t[3]]
    if not ticks:
        return None
    hit = window["steps"].sum() \
        / (window["steps"].shape[0] * window["decode_steps"])
    moved = costs_nemotron_h.decode_step_bytes(
        record["config"], sum(t[3] for t in ticks) / len(ticks),
        sum(t[5] for t in ticks) / len(ticks), hit)
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
