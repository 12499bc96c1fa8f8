"""Share of the decode_step program's device time spent under the
hyper-connections' named scopes (`mhc/coeff`: the stream norm, the
projection, the sigmoids and the Sinkhorn iterations; `mhc/pre`, `mhc/post`:
the streams read and written), read as expert_time_pct reads `moe/`."""
from benchmarks.harness import serve_cell_xing_mhc as cell


def read(record):
    found = cell.scoped_seconds(record, "mhc/")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]["total_s"]
