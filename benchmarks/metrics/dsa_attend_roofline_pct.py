"""Least time the chip could take for one layer's gather-and-attend in a
decode step (the larger of the selected tokens' K and V rows over the
published HBM bandwidth, and q . k and p . v over them over the published
bf16 peak: costs_keye_dsa.attend_call over the replica's log of the traced
ticks), over the device time of the decode step's instructions under the
scope `dsa/attend`, a layer a step. Counts the tokens selected, each row
once, whatever gathers them."""
from benchmarks.harness import costs_keye_dsa, readers
from benchmarks.harness import serve_cell_keye_dsa as cell


def read(record):
    found = cell.scoped_seconds(record, "dsa/attend")
    step = cell.traced_step(record)
    if found is None or step is None or not found[0]:
        return None
    need = costs_keye_dsa.attend_call(record["config"], step["selected"])
    peaks = readers.device_peaks(record)
    least_s = max(need["bytes"] / peaks["hbm_bytes_s"],
                  need["flops"] / peaks["flops_bf16"])
    layers = record["config"]["num_hidden_layers"]
    return 100.0 * least_s / (found[0] / (found[1]["runs"] * layers))
