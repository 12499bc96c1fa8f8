"""Share of the decode_step program's device time spent under the sparse
path's three scopes (`dsa/index`, `dsa/select`, `dsa/attend`): scoring,
selecting and gathering-and-attending, read as expert_time_pct reads
`moe/`."""
from benchmarks.harness import serve_cell_keye_dsa as cell


def read(record):
    found = cell.scoped_seconds(record, "dsa/")
    return None if found is None else 100.0 * found[0] / found[1]["total_s"]
