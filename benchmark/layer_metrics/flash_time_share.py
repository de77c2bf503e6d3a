"""Percent of the device busy time of the traced slice spent in Pallas
kernels (``tpu_custom_call``): in these cells, flash attention forward
and backward."""

from benchmark.lib import trace


def read(record):
    return trace.share_of_busy(record.trace, *record.window,
                               trace.is_kernel)
