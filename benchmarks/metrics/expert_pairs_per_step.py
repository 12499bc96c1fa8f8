"""(token, expert) pairs that land on the experts held here, per E layer
and decode step, mean over the window (the engine's device-side expert
counters, marked at the window's edges): how near the deployment's load of
rows x experts_per_token x held / router width pairs per chip the cell
comes (a quarter: one chip's rows in place of four chips')."""
from benchmarks.harness import serve_cell_nemotron_h as cell


def read(record):
    window = cell.expert_window(record)
    if window is None:
        return None
    return float(window["pairs"].sum()) \
        / (window["pairs"].shape[0] * window["decode_steps"])
