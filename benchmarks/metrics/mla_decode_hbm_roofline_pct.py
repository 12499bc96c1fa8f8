"""Least time the chip could take to move one decode step's bytes that HAD
to move (the weights that multiply, once, with only the held experts a step
hit, from the engine's expert counters; the DISTINCT latent pages of the
decoding rows in every layer, from the replica's log of the traced ticks:
costs_sarvam_mla.decode_step_bytes, over the published HBM bandwidth), over
the decode_step program's device time a call. Bound by bytes; the
counterpart of eva_decode_hbm_roofline_pct."""
from benchmarks.harness import costs_sarvam_mla, readers
from benchmarks.harness import serve_cell_sarvam_mla as cell


def read(record):
    trace = readers.trace_of(record)
    program = readers.program(record, "decode_step")
    if not trace or not program or not program["calls"]:
        return None
    sums = cell.latent_ticks(record, trace["host_began"],
                             trace["host_ended"])
    if sums is None or not sums["steps"]:
        return None
    moved = costs_sarvam_mla.decode_step_bytes(
        record["config"], sums["latent_pages_distinct"] / sums["steps"],
        record["report"]["page_size"], cell.hit_experts(record))
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
