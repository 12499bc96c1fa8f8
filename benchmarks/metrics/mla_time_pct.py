"""Share of the decode_step program's device time spent under the latent
attention's named scopes (`mla/q`, `mla/latent`, `mla/absorb`,
`mla/attend`, `mla/out`): the kernel and its four projections, read as
expert_time_pct reads `moe/`."""
from benchmarks.harness import serve_cell_sarvam_mla as cell


def read(record):
    found = cell.scoped_seconds(record, "mla_instructions", "mla/")
    if found is None or not found[1]["total_s"]:
        return None
    return 100.0 * found[0] / found[1]["total_s"]
