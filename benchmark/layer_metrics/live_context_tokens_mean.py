"""Mean live context of a decode step: the ``context_tokens`` count of
the ``tpu_ddp.serve.decode`` spans of the traced slice (the sum of the
scheduler's lengths over the slots that decode: the K/V positions the
step has to read)."""

from benchmark.lib import program_trace


def read(record):
    counts = [e[3]["context_tokens"] for e in program_trace.spans_in(
        program_trace.of(record), "tpu_ddp.serve.decode", *record.window)
        if "context_tokens" in e[3]]
    return sum(counts) / len(counts) if counts else None
