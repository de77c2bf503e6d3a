"""Percent of the device time of ``serve_prefill``'s operations, in the
traced slice, under the scope ``ssm``: the chunked scan's share of a
prefill chunk, with its projections and the slot's state."""

from benchmark.lib import scope_time


def read(record):
    return scope_time.share(record, scope_time.SSM, "serve_prefill")
