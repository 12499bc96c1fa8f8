"""The paged kernel at 32 queries a kv head (8 query heads x the 4 positions
of a row's open block): least time the chip could take to read the K and V
pages one paged_attention call needs (the live rows' committed tokens, the
replica's `length_ticks`, and their open blocks, once a row:
costs_sdar.paged_attention_bytes, over the published HBM bandwidth), over
the kernel's measured time per call. Bound by bytes, not FLOPs."""
from benchmarks.harness import costs_sdar, readers
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    kernel = readers.ops_matching(record, "paged_attention",
                                  "paged-attention")
    mean = cell.traced_mean(record)
    if mean is None or not kernel["calls"]:
        return None
    per_call = costs_sdar.paged_attention_bytes(
        record["config"], mean["context_tokens"], mean["rows"])
    least_s = per_call / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (kernel["total_s"] / kernel["calls"])
