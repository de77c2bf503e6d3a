"""Milliseconds per engine step in which no device ran anything while the
engine built the decode step's inputs on the host: device-idle time of
the traced slice whose innermost program span is
``tpu_ddp.serve.decode.tables`` (block allocation, the numpy block tables
and per-slot vectors), over the ``tpu_ddp.serve.step`` spans."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.engine_idle_ms(
        record, ("tpu_ddp.serve.decode.tables",))
