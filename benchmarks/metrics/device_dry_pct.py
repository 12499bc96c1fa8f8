"""Share of the span read in which the device had run out of work while the
engine had some: Σ `dry_s` (the mean of the account's two bounds,
`dry_s_lower` / `dry_s_upper`) over Σ extent of the `tick` row's `timeline`
rows that ended in it (benchmarks/harness/ticktimeline.py). The engine's own
idle share: no profiler, the whole window (in a traced run up to the trace's
start) where `device_idle_pct.serve` reads 4 s. Says the whole account on
stderr, by phase, and in a traced run beside the trace's own idle seconds."""
from benchmarks.harness import ticktimeline


def read(record):
    ticktimeline.say(record)
    return ticktimeline.dry_pct(record)
