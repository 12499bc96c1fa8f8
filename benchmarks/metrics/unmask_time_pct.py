"""Share of the decode_step program's device time spent under the
`sdar/confidence` (the candidates of 512 positions over 151,936 ids and
their probabilities) and `sdar/unmask` (the rule) scopes, over the traced
block steps, read as expert_time_pct reads `moe/`."""
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    found = cell.scoped_seconds(record, "sdar/confidence/", "sdar/unmask/",
                                program="decode_step")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]["total_s"]
