"""Percent of the device time of ``serve_decode``'s operations, in the
traced slice, under the scope ``moe``: router, top-k and sort
(``moe/route``), and the gather, the two grouped products and the sum
back to tokens (``moe/experts``). The grouped products are XLA:TPU's own
custom call, whose operations carry the name ``ragged-dot-none`` and no
scope path: they are counted by that name (``scope_time.MOE``). The
shared MLP is not in it."""

from benchmark.lib import scope_time


def read(record):
    return scope_time.share(record, scope_time.MOE, "serve_decode")
