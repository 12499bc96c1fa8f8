"""Least time the chip could take to move what the routed experts of ONE
layer must move in one block step (the three matrices of every expert the
step hit, once, and each routed pair's row in and out:
costs_sdar.expert_layer_bytes with the hit experts and the pairs from the
engine's counters, over the published HBM bandwidth), over the device time
under the `moe/experts` scope a layer and traced block step (the grouped
products of moe.held_expert_sum's sorted form and the glue around them).
Bound by bytes: 32 pairs an expert are far under the products' ridge."""
from benchmarks.harness import costs_sdar, readers
from benchmarks.harness import serve_cell_nemotron_h as counting
from benchmarks.harness import serve_cell_sdar as cell


def read(record):
    window = counting.expert_window(record)
    found = cell.scoped_seconds(record, "moe/experts/", program="decode_step")
    if window is None or found is None or not found[0]:
        return None
    seconds, kept = found
    layers = window["pairs"].shape[0]
    calls = layers * window["decode_steps"]
    moved = costs_sdar.expert_layer_bytes(
        record["config"], window["steps"].sum() / calls,
        window["pairs"].sum() / calls)
    least_s = moved / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (seconds / (kept["runs"] * layers))
