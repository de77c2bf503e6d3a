"""Device milliseconds of one execution of ``serve_decode`` for a model
with recurrent state (one token for the whole slot bank, the state of
every advanced slot rewritten): mean over the executions in the traced
slice. The twin of ``decode_device_ms_per_step``, whose ``workloads``
list a test pins by equality; a ``benchmark`` issue folds the two."""

from benchmark.lib import program_trace


def read(record):
    if "layer_types" not in record.config:
        return None
    return program_trace.device_ms_per_run(record, "serve_decode")
