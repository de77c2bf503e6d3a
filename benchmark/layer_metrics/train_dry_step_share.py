"""Percent of the traced slice's LM steps dispatched after the device had
already finished the step before: the ``dry`` count of the
``tpu_ddp.lm.train_step`` spans (1 where the step before's loss was
ready as the span opened). Every step of the trainer is annotated. Idle
time with few dry steps is the device waiting on something other than
the host's next dispatch."""

from benchmark.lib import program_trace


def read(record):
    dry = [e[3]["dry"] for e in program_trace.spans_in(
        program_trace.of(record), "tpu_ddp.lm.train_step", *record.window)
        if "dry" in e[3]]
    return 100.0 * sum(dry) / len(dry) if dry else None
