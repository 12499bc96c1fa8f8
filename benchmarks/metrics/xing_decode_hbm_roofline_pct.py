"""Least time the chip could take to move one decode step's bytes that HAD
to move (the weights that multiply, once, with only the experts a step hit,
from the engine's expert counters; the DISTINCT latent pages of the
decoding rows in every layer and the rows' four residual streams through
every sublayer, from the replica's log of the traced ticks:
costs_xing_mhc.decode_step_bytes, over the published HBM bandwidth), over
the decode_step program's device time a call. Bound by bytes; the
counterpart of mla_decode_hbm_roofline_pct."""
from benchmarks.harness import costs_xing_mhc, readers
from benchmarks.harness import serve_cell_sarvam_mla as latent
from benchmarks.harness import serve_cell_xing_mhc as cell


def read(record):
    program = readers.program(record, "decode_step")
    mean = cell.traced_mean(record)
    if not program or not program["calls"] or mean is None:
        return None
    moved = costs_xing_mhc.decode_step_bytes(
        record["config"], mean["pages"], record["report"]["page_size"],
        mean["rows"], latent.hit_experts(record))
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
