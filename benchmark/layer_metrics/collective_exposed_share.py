"""Percent of the traced slice in which a collective was in flight on a
device and nothing else ran there, averaged over the devices."""

from benchmark.lib import trace


def read(record):
    lo, hi = record.window
    return (100.0 * trace.collective_exposed_seconds(record.trace, lo, hi)
            * 1e9 / (hi - lo))
