"""Device milliseconds of one execution of the program ``serve_prefill``
(one ``prefill_chunk``-token slice of one prompt): mean over its
executions in the traced slice, from the device plane's "XLA Modules"
line."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.device_ms_per_run(record, "serve_prefill")
