"""The serving engine's tally: ``tpu_ddp.serve.tally`` markers, which
``ServeEngine.step()`` puts into the trace at the first step after every
half second, each carrying the engine's running totals since it was
built (``tpu_ddp/utils/profiling.py`` names the counts). The differences
between the first and the last tally of the traced slice are sums over
every step between them, whether or not a burst annotated the step."""

from benchmark.lib import program_trace

NAME = "tpu_ddp.serve.tally"


def between(record):
    """``(differences, last)``: each count of the last tally of the
    traced slice less the first's, and the last tally itself; ``None``
    where the slice holds fewer than two (a program without tallies)."""
    marks = program_trace.spans_in(program_trace.of(record), NAME,
                                   *record.window)
    if len(marks) < 2:
        return None
    first, last = marks[0][3], marks[-1][3]
    return {k: last[k] - first[k] for k in last if k in first}, last


def ratio(record, top: str, bottom: str, scale: float = 1.0):
    """``scale`` x the difference of ``top`` over that of ``bottom``;
    ``None`` where either is missing or the bottom did not move."""
    got = between(record)
    if got is None:
        return None
    diff, _ = got
    if top not in diff or not diff.get(bottom):
        return None
    return scale * diff[top] / diff[bottom]
