"""Milliseconds per engine step in which no device ran anything while the
engine read the step's results back and handed them out: device-idle
time of the traced slice whose innermost program span is
``tpu_ddp.serve.decode.fetch`` (the blocking readbacks: what is left of
them once the device has finished) or ``tpu_ddp.serve.decode.emit`` (the
per-slot loop: stamps, callbacks, retire), over the
``tpu_ddp.serve.step`` spans."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.engine_idle_ms(
        record, ("tpu_ddp.serve.decode.fetch", "tpu_ddp.serve.decode.emit"))
