"""PREFILL_CHUNK events over engine ticks in the window: the share of
ticks in which decoding rows also waited for a prefill chunk."""


def read(record):
    ticks = record["report"]["ticks"]
    if not ticks:
        return None
    t0, t1 = record["t0"], record["t1"]
    chunks = sum(1 for _rid, event, ts, _a in record["report"]["events"]
                 if event == "PREFILL_CHUNK" and t0 <= ts < t1)
    return 100.0 * chunks / len(ticks)
