"""95th percentile of the time from when a request was due to its first
token, over the requests due in the window. Per-layer until a later
benchmark issue finds it steady enough to carry a bound."""


def read(record):
    return record.counters.get("ttft_p95_ms")
