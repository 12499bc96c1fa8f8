"""The sparse path's share of the bytes a decode step has to move, by the
count alone: the index keys the rows scored and the K and V rows of the
tokens they selected, beside the weights that multiply with the held
experts a step hit and the rows written (costs_keye_dsa.decode_step_bytes
over the window's log)."""
from benchmarks.harness import costs_keye_dsa, readers
from benchmarks.harness import serve_cell_keye_dsa as cell


def read(record):
    sums = cell.dsa_ticks(record, *readers.window(record))
    if sums is None or not sums["steps"]:
        return None
    moved = costs_keye_dsa.decode_step_bytes(
        record["config"], sums["index_rows_scanned"] / sums["steps"],
        sums["sparse_rows_selected"] / sums["steps"],
        sums["decode_rows"] / sums["steps"], cell.hit_experts(record))
    return 100.0 * moved["cache"] / moved["total"]
