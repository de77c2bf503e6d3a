"""Percent of ``serve_decode``'s device time under the scope ``retention``:
a power-retention layer's mixer half, its projections and gate, the
state's read, readout and update (``retention/state``) and its output
projection (``scope_time.share``, every step of the slice). Nothing where
the program has no such scope (any other model, the parent)."""

from benchmark.lib import scope_time

RETENTION = ("retention",)


def read(record):
    return scope_time.share(record, RETENTION, "serve_decode")
