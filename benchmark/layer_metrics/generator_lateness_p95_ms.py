"""95th percentile of how long after it was due a request was submitted:
the load generator shares one thread with the engine loop."""


def read(record):
    return record.counters.get("generator_lateness_p95_ms")
