"""Prompt tokens prefilled per engine step: the ``tokens`` counts (real
prompt tokens, not the padded chunk) of the ``tpu_ddp.serve.prefill``
spans of the traced slice, over its ``tpu_ddp.serve.step`` spans."""

from benchmark.lib import program_trace


def read(record):
    prog = program_trace.of(record)
    steps = program_trace.spans_in(prog, program_trace.STEP, *record.window)
    chunks = program_trace.spans_in(prog, "tpu_ddp.serve.prefill",
                                    *record.window)
    if not steps or not chunks:
        return None
    return sum(e[3].get("tokens", 0) for e in chunks) / len(steps)
