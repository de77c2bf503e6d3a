"""The retention state's share of the HBM roofline in a decode step,
whatever implements it: each decoded row's ``S`` and ``z`` in every layer
read once (``shapes_retention.state_read_bytes`` at the tally's
``decode_rows`` over ``decode_steps``, every step between the slice's
first and last tally), over the chip's published bandwidth times the mean
device time a ``serve_decode`` execution spends under the scope
``retention/state`` (the read of the slots' state, the readout from it,
the update written back; in this family's program the one scope named
``state``). The write is not counted: a form that folds the last few
positions into the state every few steps need not make it each step, so
no form can read past 100%. Nothing where the program has no such scope,
the tally no such counts, or the configuration no power retention."""

from benchmark.lib import scope_time, shapes, shapes_retention, tally

STATE = ("state",)


def read(record):
    if "power_retention" not in record.config:
        return None
    ms = scope_time.per_run_ms(record, STATE, "serve_decode")
    got = tally.between(record)
    if ms is None or got is None:
        return None
    diff, _ = got
    steps = diff.get("decode_steps")
    if not steps:
        return None
    _, peak_bytes = shapes.peak(record.device["kind"])
    need = shapes_retention.state_read_bytes(
        record.config, diff["decode_rows"] / steps)
    return 100.0 * need / (peak_bytes * ms[0] / 1e3)
