"""The state-space mixers' share of the HBM roofline in a decode step:
the state layers' weights once plus the state of the advanced slots read
and written once (``shapes_hybrid.ssm_step_bytes`` at the mean
``state_slots`` of the traced decode steps, ``scope_time.decode_steps``),
over the chip's published bandwidth times the mean device time under the
scope ``ssm`` per execution of ``serve_decode``."""

from benchmark.lib import scope_time, shapes, shapes_hybrid


def read(record):
    ms = scope_time.per_run_ms(record, scope_time.SSM, "serve_decode")
    steps = scope_time.decode_steps(record)
    if ms is None or not steps:
        return None
    slots = sum(n for _, n, _ in steps) / len(steps)
    _, peak_bytes = shapes.peak(record.device["kind"])
    need = shapes_hybrid.ssm_step_bytes(record.config, slots)
    return 100.0 * need / (peak_bytes * ms[0] / 1e3)
