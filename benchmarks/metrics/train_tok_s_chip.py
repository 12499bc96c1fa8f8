"""Tokens of the whole steps that cover the window (each ended by fetching
the loss), over the time those steps took and the chips."""


def read(record):
    return record["window_tokens"] / (record["t1"] - record["t0"]) \
        / record["chips"]
