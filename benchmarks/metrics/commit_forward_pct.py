"""Of the row-forwards dispatched between the window's marks, the share
that were commits (the engine's `commit_forwards` / `block_forwards`): a
commit yields no token, it keeps a finished block's K/V. The traffic fixes
it near 30 (one forward in 2, 3 or 5); fusing a block's commit with the next
block's first forward would take it to 0."""
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    window = cell.block_window(record)
    if window is None or not window["forwards"]:
        return None
    return 100.0 * window["commits"] / window["forwards"]
