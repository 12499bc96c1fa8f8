"""Least time the chip could take for one call of the latent decode kernel
(one layer, one step) over the kernel's measured time a call: the larger of
the DISTINCT pages the decoding rows hold, each read once as key and value,
over the published HBM bandwidth, and the absorbed products over the rows'
cached tokens, 64 heads x (576 + 512) x 2, over the published bf16 peak
(costs_sarvam_mla.attention_call, from the replica's log of the traced
ticks). Counted once a page, so no later form, one that reads a shared
document once for all its rows included, can read over 100."""
from benchmarks.harness import costs_sarvam_mla, readers
from benchmarks.harness import serve_cell_sarvam_mla as cell


def read(record):
    trace = readers.trace_of(record)
    kernel = readers.ops_matching(record, "latent_attention",
                                  "latent-attention")
    if not trace or not kernel["calls"]:
        return None
    sums = cell.latent_ticks(record, trace["host_began"],
                             trace["host_ended"])
    if sums is None or not sums["steps"]:
        return None
    need = costs_sarvam_mla.attention_call(
        record["config"], sums["latent_pages_distinct"] / sums["steps"],
        sums["latent_rows_attended"] / sums["steps"],
        record["report"]["page_size"])
    peaks = readers.device_peaks(record)
    least_s = max(need["bytes"] / peaks["hbm_bytes_s"],
                  need["flops"] / peaks["flops_bf16"])
    return 100.0 * least_s / (kernel["total_s"] / kernel["calls"])
