"""Milliseconds per engine step in which no device ran anything while the
engine was handing work to the device: device-idle time of the traced
slice whose innermost program span is ``tpu_ddp.serve.decode.dispatch``
(uploads, the jitted call, ``pool.commit``) or ``tpu_ddp.serve.prefill``
(which has no children, so this is its self time: chunk build, upload,
dispatch), over the ``tpu_ddp.serve.step`` spans."""

from benchmark.lib import program_trace


def read(record):
    return program_trace.engine_idle_ms(
        record, ("tpu_ddp.serve.decode.dispatch", "tpu_ddp.serve.prefill"))
