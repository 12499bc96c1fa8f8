"""Forward + backward FLOPs a token requires (costs.train_flops_per_token,
recompute not counted) times tokens/s/chip, over the published bf16 peak."""
from benchmarks.harness import costs, readers


def read(record):
    rate = record["window_tokens"] / (record["t1"] - record["t0"]) \
        / record["chips"]
    need = costs.train_flops_per_token(record["config"],
                                       record["traffic"]["sequence"])
    return 100.0 * need * rate / readers.device_peaks(record)["flops_bf16"]
