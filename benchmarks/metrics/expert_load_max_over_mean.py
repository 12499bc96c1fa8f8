"""Tokens routed to the fullest held expert over the mean held expert's,
over the window, in the E layer where that ratio is worst (the engine's
device-side expert counters): 1.0 is perfect balance. A layer none of
whose held experts was routed a token in the window has no load to
balance and is left out."""
from benchmarks.harness import serve_cell_nemotron_h as cell


def read(record):
    window = cell.expert_window(record)
    if window is None:
        return None
    pairs = window["pairs"].astype(float)
    loaded = pairs[pairs.sum(1) > 0]
    if not len(loaded):
        return None
    return float((loaded.max(1) / loaded.mean(1)).max())
