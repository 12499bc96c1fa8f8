"""Decoding rows per engine tick over max_batch, mean over the window's
ticks (the replica's per-tick log)."""


def read(record):
    ticks = record["report"]["ticks"]
    if not ticks:
        return None
    return 100.0 * sum(t[3] for t in ticks) / len(ticks) \
        / record["report"]["max_batch"]
