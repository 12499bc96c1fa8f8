"""Least time the chip could take for one prefill chunk of the largest
bucket (512 tokens: nine chunks in ten of this traffic) over the
chunk_prefill program's median device time a call: the larger of the
chunk's byte floor (the weights once, the rows it attends, the streams)
over the published HBM bandwidth and its operations (2 a parameter a token
through the dense parts and the CHOSEN (token, expert) pairs, the attention
products in the cheaper of their forms: costs_xing_mhc.chunk, at the mean
context of the traced span's chunks) over the published bf16 peak. The work
is counted the same whatever implements it, so a sorted expert form moves
the reading and not the yardstick."""
from benchmarks.harness import costs_xing_mhc, readers
from benchmarks.harness import serve_cell_xing_mhc as cell


def read(record):
    program = readers.program(record, "chunk_prefill")
    mean = cell.traced_mean(record)
    if not program or not program.get("median_ms") or mean is None \
            or not mean["chunk_rows_read"]:
        return None
    need = costs_xing_mhc.chunk(
        record["config"], record["config"]["engine"]["prefill_buckets"][-1],
        mean["chunk_rows_read"])
    peaks = readers.device_peaks(record)
    least_s = max(need["bytes"] / peaks["hbm_bytes_s"],
                  need["flops"] / peaks["flops_bf16"])
    return 100.0 * least_s / (program["median_ms"] / 1e3)
