"""Least time the chip could take to move one decode step's bytes that HAD
to move (the weights that multiply, once, with only the held experts a step
hit, from the engine's expert counters; the K and V pages of the decoding
rows' lengths in the attending layers and the rows' windows in the conv
layers, from the replica's log of the traced ticks:
costs_lfm2.decode_step_bytes, over the published HBM bandwidth), over the
decode_step program's device time a call. Bound by bytes."""
from benchmarks.harness import costs_lfm2, readers
from benchmarks.harness import serve_cell_lfm2 as cell
from benchmarks.harness.serve_cell_sarvam_mla import hit_experts


def read(record):
    program = readers.program(record, "decode_step")
    mean = cell.traced_mean(record)
    if not program or not program["calls"] or mean is None:
        return None
    moved = costs_lfm2.decode_step_bytes(
        record["config"], mean["context_tokens"], mean["rows"],
        hit_experts(record))
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
