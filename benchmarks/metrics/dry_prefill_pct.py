"""Of the seconds the device was dry in the span read, the share that lay
under the visit's phase `prefill` and its parts (`prefill/chunk`: staging and
dispatching a chunk; `prefill/finish`: a finished prompt's page write, radix
insert and first token), from the `tick` row's `dry_by_phase`
(benchmarks/harness/ticktimeline.py). 0 with nothing dry."""
from benchmarks.harness import ticktimeline


def read(record):
    return ticktimeline.phase_share_pct(record, ticktimeline.PREFILL)
