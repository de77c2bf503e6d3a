"""Percent of the device time of the traced slice's operations spent in
the optimizer update: operations whose ``named_scope`` path holds
``optimizer`` (AdamW over the f32 state in ``lm_train_step``)."""

from benchmark.lib import program_trace


def read(record):
    shares = program_trace.scope_share(record, ("optimizer",))
    return shares[0] if shares else None
