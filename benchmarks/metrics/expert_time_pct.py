"""Share of the decode_step program's device time spent under the expert
layers' named scopes (`moe/route`, `moe/latent`, `moe/experts`,
`moe/shared`): each device event of the traced decode steps is an
instruction, the compiled program's text says under which scope it was
traced (a loop's own event is left out: its body's have theirs)."""
from benchmarks.harness import serve_cell_nemotron_h as cell


def read(record):
    found = cell.scoped_seconds(record, "moe/")
    if found is None or not found[1]["total_s"]:
        return None
    return 100.0 * found[0] / found[1]["total_s"]
