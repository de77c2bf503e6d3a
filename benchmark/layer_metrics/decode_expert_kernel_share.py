"""Percent of the device time of ``serve_decode``'s operations, in the
traced slice, spent in the Pallas kernel ``grouped_matmul`` (the expert
layer's two grouped products over the sorted assignments, each held
expert's matrix streamed once past rows resident on chip; two calls a
layer): the operations whose scope path holds the kernel's ``name=``
(``jit(serve_decode)/mlp/moe/experts/jit(_impl)/grouped_matmul/
pallas_call``).

The kernel's name in the trace is the counter that says the mechanism
engaged: a program whose grouped products are XLA's own custom call
(``ragged-dot-none``: the parent; shapes outside the kernel's predicate)
has no such operation, and the reader then returns nothing."""

from benchmark.lib import scope_time

KERNEL = ("grouped_matmul",)


def read(record):
    return scope_time.share(record, KERNEL, "serve_decode")
