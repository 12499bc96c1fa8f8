"""Share of the decode_step program's device time spent under the scope
`dsa/select`: the exact top-k of every row's scores (the threshold's
counting passes, the ties, the compaction to positions)."""
from benchmarks.harness import serve_cell_keye_dsa as cell


def read(record):
    found = cell.scoped_seconds(record, "dsa/select")
    return None if found is None else 100.0 * found[0] / found[1]["total_s"]
