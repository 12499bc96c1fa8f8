"""The recurrent state's share of the bytes a decode tick has to move
(costs_hybrid.decode_tick_bytes over the window's tick log): how much of
the tick the state-space mechanism is, by the count alone."""
from benchmarks.harness import costs_hybrid, readers


def read(record):
    if "mamba_d_state" not in record["config"]:
        return None
    moved = costs_hybrid.window_tick_bytes(record, *readers.window(record))
    return 100.0 * moved["state"] / moved["total"] if moved else None
