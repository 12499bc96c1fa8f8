"""The paged kernel at 64-wide heads over the packed pool: least time the
chip could take to read the K and V pages one paged_attention call needs
(bytes of the decoding rows' own lengths, the replica's `length_ticks`, at
the heads' own 64 lanes, by
costs_lfm2.paged_attention_bytes, over the published HBM bandwidth), over
the kernel's measured time per call. Bound by bytes, not FLOPs."""
from benchmarks.harness import costs_lfm2, readers
from benchmarks.harness import serve_cell_lfm2 as cell


def read(record):
    kernel = readers.ops_matching(record, "paged_attention",
                                  "paged-attention")
    mean = cell.traced_mean(record)
    if mean is None or not kernel["calls"]:
        return None
    per_call = costs_lfm2.paged_attention_bytes(
        record["config"], mean["context_tokens"])
    least_s = per_call / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (kernel["total_s"] / kernel["calls"])
