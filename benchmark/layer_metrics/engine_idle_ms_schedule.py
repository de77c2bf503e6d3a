"""Milliseconds per engine step in which no device ran anything while the
engine was scheduling: device-idle time of the traced slice whose
innermost program span is ``tpu_ddp.serve.schedule`` (chaos / subscriber
hooks, deadline shedding, admission, the picks of slots) or one of its
``tpu_ddp.serve.admit`` markers, over the ``tpu_ddp.serve.step`` spans."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.engine_idle_ms(
        record, ("tpu_ddp.serve.schedule", "tpu_ddp.serve.admit"))
