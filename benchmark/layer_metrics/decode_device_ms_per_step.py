"""Device milliseconds of one execution of the program ``serve_decode``
(one token for the whole slot bank): mean over its executions in the
traced slice, from the device plane's "XLA Modules" line."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.device_ms_per_run(record, "serve_decode")
