"""What a finished prompt costs the stepping thread: Δ`prefill_finish_s` /
Δ`prompts_finished` of the accel `tick` row's counters — the seconds
`_finish_prefill` ran (the owned pages' write, the radix insert,
`first_token`), timed inside the `prefill` phase apart from the chunks'
staging and dispatch, over the prompts finished. In a traced run without
the slow visits that ended at or after trace.host_began
(benchmarks/harness/tickstalls.py). None where the program does not time
it (before PR 39) or no prompt finished in the span read."""
from benchmarks.harness import tickstalls


def read(record):
    window = tickstalls.visits(record)
    if window is None or "prefill_finish_s" not in window["counters"]:
        return None
    finished = window["counters"].get("prompts_finished", 0.0)
    return 1e3 * window["counters"]["prefill_finish_s"] / finished \
        if finished > 0 else None
