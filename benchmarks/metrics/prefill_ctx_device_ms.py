"""Device time of the chunk_prefill calls of the traced span over the
cached rows their chunks attended (the engine's counter `prefill_ctx_rows`:
a chunk's rows up to its last real token, from the replica's log of the
traced ticks), in ms a thousand rows: a chunk's time read against the
context it attended, which prefill_chunk_device_ms (a median over chunks
of every context) cannot say."""
from benchmarks.harness import readers
from benchmarks.harness import serve_cell_sarvam_mla as cell


def read(record):
    trace = readers.trace_of(record)
    row = readers.program(record, "chunk_prefill")
    if not trace or not row or not row["calls"]:
        return None
    sums = cell.latent_ticks(record, trace["host_began"],
                             trace["host_ended"])
    if sums is None or not sums["prefill_ctx_rows"]:
        return None
    return 1e3 * row["total_s"] / (sums["prefill_ctx_rows"] / 1e3)
