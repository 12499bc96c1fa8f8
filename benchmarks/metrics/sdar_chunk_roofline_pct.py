"""Least time the chip could take for one prefill chunk of the largest
bucket (512 tokens) over the chunk_prefill program's median device time a
call, as lfm2_chunk_roofline_pct reckons its own: the larger of the chunk's
byte floor (the weights once, no head, and of the 128 experts a layer those
that the chunk's 512 token rows hit, from the chunks' OWN counters: the
engine counts, a chunk of the largest bucket, the experts it routed a token
to; the K/V rows it attends and writes) over the published HBM bandwidth and
its operations (2 a parameter a token through the attentions, the routers
and the chosen pairs, the attention products over the (query, key) pairs
under the block mask: costs_sdar.chunk, at the mean context of the window's
chunks) over the published bf16 peak."""
from benchmarks.harness import costs_sdar, readers
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    program = readers.program(record, "chunk_prefill")
    mean = cell.traced_mean(record)
    hit = cell.chunk_hit_experts(record)
    if not program or not program.get("median_ms") or mean is None \
            or not mean["chunk_rows_read"] or hit is None:
        return None
    need = costs_sdar.chunk(
        record["config"], record["config"]["engine"]["prefill_buckets"][-1],
        mean["chunk_rows_read"], hit)
    peaks = readers.device_peaks(record)
    least_s = max(need["bytes"] / peaks["hbm_bytes_s"],
                  need["flops"] / peaks["flops_bf16"])
    return 100.0 * least_s / (program["median_ms"] / 1e3)
