"""Least time the chip could take for one prefill chunk of the largest
bucket (512 tokens: four chunks in five of this traffic) over the
chunk_prefill program's median device time a call: the larger of the
chunk's byte floor (the weights once, the K/V rows it attends and writes)
over the published HBM bandwidth and its operations (2 a parameter a token
through the mixers, the dense layers and the chosen pairs on the experts
held, the attention products over the (query, key) pairs:
costs_lfm2.chunk, at the mean context of the window's chunks) over the
published bf16 peak. The work is counted the same whatever implements it."""
from benchmarks.harness import costs_lfm2, readers
from benchmarks.harness import serve_cell_lfm2 as cell


def read(record):
    program = readers.program(record, "chunk_prefill")
    mean = cell.traced_mean(record)
    if not program or not program.get("median_ms") or mean is None \
            or not mean["chunk_rows_read"]:
        return None
    need = costs_lfm2.chunk(
        record["config"], record["config"]["engine"]["prefill_buckets"][-1],
        mean["chunk_rows_read"])
    peaks = readers.device_peaks(record)
    least_s = max(need["bytes"] / peaks["hbm_bytes_s"],
                  need["flops"] / peaks["flops_bf16"])
    return 100.0 * least_s / (program["median_ms"] / 1e3)
