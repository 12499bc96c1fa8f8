"""The latent pages' share of the bytes this program's decode step reads,
by the count alone: the pages the decoding rows hold counted a ROW (the
kernel reads a shared document's pages once for every row on it) beside the
weights that multiply with the held experts a step hit
(costs_sarvam_mla.decode_step_bytes over the window's log)."""
from benchmarks.harness import costs_sarvam_mla, readers
from benchmarks.harness import serve_cell_sarvam_mla as cell


def read(record):
    sums = cell.latent_ticks(record, *readers.window(record))
    if sums is None or not sums["steps"]:
        return None
    moved = costs_sarvam_mla.decode_step_bytes(
        record["config"], sums["latent_pages_rowwise"] / sums["steps"],
        record["report"]["page_size"], cell.hit_experts(record))
    return 100.0 * moved["cache"] / moved["total"]
