"""Least time the chip could take to read the pages one paged_attention
call needs at this model's rows (one layer's K and V of every page the
decoding rows HOLD, from the replica's log of pages held, over the
published HBM bandwidth), over the kernel's measured time a call: the
shared kernel's share of its roofline at 32 kv heads and one query a kv
head, where paged_attn_roofline_pct's count (lengths rounded to pages)
would be four times too high. Bound by bytes."""
from benchmarks.harness import costs_evabyte, readers


def read(record):
    trace = readers.trace_of(record)
    kernel = readers.ops_matching(record, "paged_attention",
                                  "paged-attention")
    if not trace or not kernel["calls"]:
        return None
    pages = costs_evabyte.window_pages(record, trace["host_began"],
                                       trace["host_ended"])
    if pages is None:
        return None
    per_call = pages * costs_evabyte.page_bytes(
        record["config"], record["report"]["page_size"], layers=1)
    least_s = per_call / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (kernel["total_s"] / kernel["calls"])
