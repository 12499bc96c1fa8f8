"""1 - free_pages / num_pages, mean over the window's ticks."""


def read(record):
    ticks = record["report"]["ticks"]
    if not ticks:
        return None
    pages = record["report"]["num_pages"]
    return 100.0 * (1.0 - sum(t[2] for t in ticks) / len(ticks) / pages)
