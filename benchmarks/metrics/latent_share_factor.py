"""Latent pages the decoding rows hold a step counted a row, over the same
pages counted once, over the window's decode steps (the engine's counters
`latent_pages_rowwise` / `latent_pages_distinct`): what a kernel that reads
a shared document once for all the rows on it would save. 1.0 where no two
rows share a page."""
from benchmarks.harness import readers


def read(record):
    stats = record["closed"]["stats"]
    if "latent_pages_distinct" not in stats:
        return None
    distinct = readers.stat_delta(record, "latent_pages_distinct")
    if not distinct:
        return None
    return readers.stat_delta(record, "latent_pages_rowwise") / distinct
