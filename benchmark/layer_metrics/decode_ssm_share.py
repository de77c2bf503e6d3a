"""Percent of the device time of ``serve_decode``'s operations, in the
traced slice, under the scope ``ssm``: the state-space mixers
(projections, convolution, recurrence, gated norm) with the reads and
writes of the state pool (``ssm/state``)."""

from benchmark.lib import scope_time


def read(record):
    return scope_time.share(record, scope_time.SSM, "serve_decode")
