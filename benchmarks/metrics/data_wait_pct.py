"""Time the train loop waited for its next batch over the window's time
(host clock; the batch is prefetched one step ahead)."""


def read(record):
    return 100.0 * sum(record["window_waits"]) \
        / (record["t1"] - record["t0"])
