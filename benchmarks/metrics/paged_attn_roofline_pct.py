"""Least time the chip could take to read the K and V pages one
paged_attention call needs (bytes from the tick log's context lengths, by
costs.paged_attention_bytes, over the published HBM bandwidth), over the
kernel's measured time per call. Bound by bytes, not FLOPs."""
from benchmarks.harness import costs, readers


def read(record):
    trace = readers.trace_of(record)
    kernel = readers.ops_matching(record, "paged_attention",
                                  "paged-attention")
    if not trace or not kernel["calls"]:
        return None
    ticks = [t for t in record["report"]["ticks"]
             if trace["host_began"] <= t[0] < trace["host_ended"] and t[3]]
    if not ticks:
        return None
    page = record["report"]["page_size"]
    per_call = sum(costs.paged_attention_bytes(record["config"], t[5], page)
                   for t in ticks) / len(ticks)
    least_s = per_call / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (kernel["total_s"] / kernel["calls"])
