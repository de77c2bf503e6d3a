"""Percent of the decode steps dispatched with the step before still in
flight (``ahead``) at which the device had already finished it: the
tally's ``dry_steps`` over its ``decode_ahead``, between the first and
the last ``tpu_ddp.serve.tally`` of the traced slice (every step between
them). A dry step is one at which the host came back late; idle time in
steps that are not dry is the device waiting on something else."""

from benchmark.lib import tally


def read(record):
    return tally.ratio(record, "dry_steps", "decode_ahead", 100.0)
