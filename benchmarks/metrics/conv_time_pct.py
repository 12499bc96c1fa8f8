"""Share of the decode_step and chunk_prefill programs' device time spent
under the gated short convolutions' named scopes (`conv/in`: the 2048 ->
6144 product and the gate; `conv/filter`: the taps and the window;
`conv/out`: the gate and the 2048 -> 2048 product), over the traced runs of
both, read as expert_time_pct reads `moe/`."""
from benchmarks.harness import serve_cell_lfm2 as cell


def read(record):
    found = [cell.scoped_seconds(record, "conv/", program=program)
             for program in cell.SCOPES_OF]
    found = [f for f in found if f is not None]
    total = sum(kept["total_s"] for _, kept in found)
    if not found or not total:
        return None
    return 100.0 * sum(seconds for seconds, _ in found) / total
