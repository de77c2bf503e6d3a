"""Percent of the device time of ``serve_prefill``'s operations, in the
traced slice, spent in the Pallas kernel ``grouped_matmul`` (the expert
layer's two grouped products over a chunk's sorted assignments; two
calls a layer): the twin of ``decode_expert_kernel_share`` for the
chunk program. Nothing where the program's grouped products are XLA's
own custom call (the parent; shapes outside the kernel's predicate)."""

from benchmark.lib import scope_time

KERNEL = ("grouped_matmul",)


def read(record):
    return scope_time.share(record, KERNEL, "serve_prefill")
