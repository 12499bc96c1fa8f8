"""Tokens handed out over row-forwards dispatched, between the window's
marks (the engine's `block_tokens_out` / `block_forwards`): what a forward
of a row's block yields. The traffic fixes it near 1.2 (4 tokens a block
over 2, 3 or 5 forwards, a third each); it falls when the scheduler spends a
forward it need not (a block forwarded again after its commit, a row stepped
past its last block)."""
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    window = cell.block_window(record)
    if window is None or not window["forwards"]:
        return None
    return window["tokens"] / window["forwards"]
