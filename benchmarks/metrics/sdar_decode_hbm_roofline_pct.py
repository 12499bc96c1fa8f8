"""Least time the chip could take to move one block step's bytes that HAD to
move (the weights of six layers and the head once, with only the experts a
step hit, from the engine's expert counters; the K and V pages of the live
rows and their blocks' rows, from the replica's log of the traced ticks; the
logits of the rows' block positions once: costs_sdar.block_step_bytes, over
the published HBM bandwidth), over the decode_step program's device time a
call. Bound by bytes."""
from benchmarks.harness import costs_sdar, readers
from benchmarks.harness import serve_cell_sdar as cell
from benchmarks.harness.serve_cell_sarvam_mla import hit_experts


def read(record):
    program = readers.program(record, "decode_step")
    mean = cell.traced_mean(record)
    if not program or not program["calls"] or mean is None:
        return None
    moved = costs_sdar.block_step_bytes(
        record["config"], mean["context_tokens"], mean["rows"],
        hit_experts(record))
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
