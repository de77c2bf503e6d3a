"""The whole decode program's share of the HBM roofline for a model with
recurrent state: for each traced decode step, the bytes it has to move
(the weights once, the state of that step's own ``state_slots`` read and
written, the K/V of its own ``context_tokens``) over the chip's published
bandwidth times the device time of that step's ``serve_decode``
execution; mean over the steps (``scope_time.decode_steps``: span paired
to execution as ``decode_program_hbm_util`` does, or the window's means
where the slice holds no annotated step)."""

from benchmark.lib import scope_time, shapes, shapes_hybrid


def read(record):
    steps = scope_time.decode_steps(record)
    if not steps:
        return None
    _, peak_bytes = shapes.peak(record.device["kind"])
    shares = [shapes_hybrid.decode_step_bytes(record.config, slots, ctx)
              / (peak_bytes * ms / 1e3) for ms, slots, ctx in steps]
    return 100.0 * sum(shares) / len(shares)
