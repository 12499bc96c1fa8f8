"""Of the seconds the device was dry in the span read, the share that lay
under `grow` + `stage` + `dispatch`: the host staging a decode step in front
of a chip with nothing to run, from the `tick` row's `dry_by_phase`
(benchmarks/harness/ticktimeline.py). 0 with nothing dry."""
from benchmarks.harness import ticktimeline


def read(record):
    return ticktimeline.phase_share_pct(record, ticktimeline.STAGE)
