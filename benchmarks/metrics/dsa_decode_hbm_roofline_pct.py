"""Least time the chip could take to move one decode step's bytes that HAD
to move (the weights that multiply, once, with only the held experts a step
hit, from the engine's expert counters; the index keys the rows scored, the
K and V rows of the tokens they selected and the rows they wrote, in every
layer, from the replica's log of the traced ticks:
costs_keye_dsa.decode_step_bytes, over the published HBM bandwidth), over
the decode_step program's device time a call. Bound by bytes; the cell's
share of the whole step, the counterpart of mla_decode_hbm_roofline_pct."""
from benchmarks.harness import costs_keye_dsa, readers
from benchmarks.harness import serve_cell_keye_dsa as cell


def read(record):
    program = readers.program(record, "decode_step")
    step = cell.traced_step(record)
    if not program or not program["calls"] or step is None:
        return None
    moved = costs_keye_dsa.decode_step_bytes(
        record["config"], step["scored"], step["selected"], step["rows"],
        cell.hit_experts(record))
    least_s = moved["total"] / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (program["total_s"] / program["calls"])
