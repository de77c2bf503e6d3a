"""Percent of the traced slice's decode steps that the engine dispatched
while the step before was still unread on the device: the ``ahead`` count
of the ``tpu_ddp.serve.decode`` spans (1 ahead, 0 where the engine was at
rest: the first step after idle, the step after a ``cancel`` or a weight
flip). The count says the mechanism engaged; a program whose spans lack
it (the engine that read every step back before the next) reads nothing."""

from benchmark.lib import program_trace


def read(record):
    ahead = [e[3]["ahead"] for e in program_trace.spans_in(
        program_trace.of(record), "tpu_ddp.serve.decode", *record.window)
        if "ahead" in e[3]]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
