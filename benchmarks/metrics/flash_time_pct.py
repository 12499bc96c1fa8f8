"""Share of the device's busy time spent in the flash_fwd / flash_bwd_*
kernels, from the trace."""
from benchmarks.harness import readers


def read(record):
    trace = readers.trace_of(record)
    flash = readers.ops_matching(record, "flash_")
    if not trace or not flash["calls"]:
        return None
    return 100.0 * flash["total_s"] / trace["busy_s"]
