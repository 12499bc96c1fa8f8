"""Phase `prefill` per tick: chunk staging and dispatch, and where a prompt
ends its write_pages and the fetch of the first token's logits (the one
place this phase waits for the device)."""
from benchmarks.harness import tickphases


def read(record):
    return tickphases.phase_ms(record, "prefill")
