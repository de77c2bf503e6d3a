"""The decode program's own share of the HBM roofline: for each traced
decode step, the bytes it has to read (the bf16 weights once, plus the
K/V of that step's own ``context_tokens``, the count on its
``tpu_ddp.serve.decode`` span) over the chip's published bandwidth times
the device time of that step's ``serve_decode`` execution; mean over the
steps. Bound by bytes: a decode step does two operations a byte.
``decode_hbm_util`` divides by the gap between tokens instead, host and
prefill chunks included."""

from benchmark.lib import program_trace, shapes


def read(record):
    prog = program_trace.of(record)
    lo, hi = record.window
    _, peak_bytes = shapes.peak(record.device["kind"])
    per = [iv for ivs in
           program_trace.runs(prog, "serve_decode", lo, hi).values()
           for iv in ivs]
    shares = []
    for _, start, dur, counts in program_trace.spans_in(
            prog, "tpu_ddp.serve.decode", lo, hi):
        # The engine waits for its step, so the execution a span
        # dispatched starts inside the span.
        mine = [e - s for s, e in per if start <= s < start + dur]
        if len(mine) == 1 and "context_tokens" in counts:
            need = shapes.decode_step_bytes(record.config,
                                            counts["context_tokens"])
            shares.append(need / (peak_bytes * mine[0] / 1e9))
    return 100.0 * sum(shares) / len(shares) if shares else None
