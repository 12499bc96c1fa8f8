"""Least time the chip could take to move what the routed experts of one E
layer must move in one decode step (the two matrices of every held expert
that was hit, once, and each pair's latent row in and out: costs_nemotron_h.
expert_matmul_bytes with the hit experts and pairs from the engine's
counters, over the published HBM bandwidth), over the device time under the
`moe/experts` scope per E layer and traced decode step (the einsums of
moe.held_expert_sum). Bound by bytes. A form that reads every held
expert's matrices whatever the routing reads low by the share of held
experts a step did not hit: those bytes did not have to move."""
from benchmarks.harness import costs_nemotron_h, readers
from benchmarks.harness import serve_cell_nemotron_h as cell


def read(record):
    window = cell.expert_window(record)
    found = cell.scoped_seconds(record, "moe/experts")
    if window is None or found is None or not found[0]:
        return None
    seconds, kept = found
    layers = window["pairs"].shape[0]
    calls = layers * window["decode_steps"]
    moved = costs_nemotron_h.expert_matmul_bytes(
        record["config"], window["steps"].sum() / calls,
        window["pairs"].sum() / calls)
    least_s = moved / readers.device_peaks(record)["hbm_bytes_s"]
    return 100.0 * least_s / (seconds / (kept["runs"] * layers))
