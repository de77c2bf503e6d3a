"""Milliseconds a train step waits for the loader: host clock around
``next()`` on the loader the epoch loop iterates, over the whole window."""


def read(record):
    c = record.counters
    if not c.get("data_batches"):
        return None
    return 1e3 * c["data_wait_s"] / c["data_batches"]
