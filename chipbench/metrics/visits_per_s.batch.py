"""Partition visits per second of the window: ``SessionResult.stats
["visits"]`` summed over the window's batches, over the window's wall
time."""


def read(record):
    c = record.counters
    if "visits" not in c or not c.get("window_s"):
        return None
    return c["visits"] / c["window_s"]
