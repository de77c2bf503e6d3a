"""Percent of the traced slice in which no operation ran on a device,
averaged over the devices (serving cells)."""

from benchmark.lib import trace


def read(record):
    return trace.idle_share(record.trace, *record.window)
