"""Process start to the start of the measured window (host clock)."""


def read(record):
    return record["setup_s"]
