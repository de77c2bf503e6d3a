"""Device milliseconds of one execution of ``serve_prefill`` for a model
with recurrent state (one ``prefill_chunk``-token slice of one prompt,
the chunked scan from the slot's incoming state): mean over the
executions in the traced slice. The twin of
``prefill_device_ms_per_chunk``, whose ``workloads`` list a test pins by
equality; a ``benchmark`` issue folds the two."""

from benchmark.lib import program_trace


def read(record):
    if "layer_types" not in record.config:
        return None
    return program_trace.device_ms_per_run(record, "serve_prefill")
