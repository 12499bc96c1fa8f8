"""FLOPs the flash kernels' calls require (from shapes, costs.flash_flops,
each device's share of batch and heads) over the published bf16 peak, over
their measured device time. Bound by FLOPs at these shapes."""
from benchmarks.harness import costs, readers


def read(record):
    trace = readers.trace_of(record)
    if not trace:
        return None
    traffic, config = record["traffic"], record["config"]
    shards = 1
    for axis in ("data", "fsdp", "tensor"):
        shards *= record["mesh"].get(axis, 1)
    need = seconds = 0.0
    for kind, needle in (("fwd", "flash_fwd"), ("bwd_kv", "flash_bwd_kv"),
                         ("bwd_q", "flash_bwd_q")):
        got = readers.ops_matching(record, needle)
        need += got["calls"] * costs.flash_flops(
            config, traffic["batch"], traffic["sequence"], kind) / shards
        seconds += got["total_s"]
    if not seconds:
        return None
    return 100.0 * need / readers.device_peaks(record)["flops_bf16"] \
        / seconds
