"""Of the rows the window's decode steps attended, the share that were
summaries of closed windows (the engine's `summary_rows` and `window_rows`
counters, from lengths alone, between the window's marks)."""


def read(record):
    opened, closed = record["opened"]["stats"], record["closed"]["stats"]
    if "summary_rows" not in closed:
        return None
    summary = closed["summary_rows"] - opened.get("summary_rows", 0)
    window = closed["window_rows"] - opened.get("window_rows", 0)
    return 100.0 * summary / (summary + window) if summary + window else None
