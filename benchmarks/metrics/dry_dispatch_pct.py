"""Of the programs the engine handed the device in the span read (decode
steps, prefill chunks, installs: the `tick` row's counter `dispatches`), the
share that found it with nothing left to run (`dry_dispatches`): one poll of
the newest program's output just before each
(benchmarks/harness/ticktimeline.py)."""
from benchmarks.harness import ticktimeline


def read(record):
    account = ticktimeline.dry(record)
    if account is None or not account["dispatches"]:
        return None
    return 100.0 * account["dry_dispatches"] / account["dispatches"]
