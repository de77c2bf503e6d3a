"""Percent of ``serve_prefill``'s device time under the scope
``retention``: a power-retention layer's mixer half in a prefill chunk,
with what reads and makes the state (``retention/carry``) and the
quadratic part within the chunk (``retention/chunk``). Nothing where the
program has no such scope (any other model, the parent).

Of the operations' events, only those that cover no other count, in the
part and in the whole: the chunked form runs its parts in ``while``
loops, and on the v5e a loop's own event spans the events of its body
(with no scope of its own), so ``scope_time.share``, which sums every
event, counts the body twice in the whole and reads about half the true
share."""

from benchmark.lib import program_trace

RETENTION = "retention"


def _leaves(events) -> list:
    """``events`` (by start, the longest and then the unscoped first)
    less each that covers the start of the next: a control-flow
    operation, whose body has events of its own."""
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or not e[1] <= nxt[1] < e[1] + e[2]]


def read(record):
    prog = program_trace.of(record)
    hit = all_ = 0
    for dev, keep in program_trace.runs(prog, "serve_prefill",
                                        *record.window).items():
        mine = sorted((e for e in prog["scopes"].get(dev, [])
                       if any(lo <= e[1] and e[1] + e[2] <= hi
                              for lo, hi in keep)),
                      key=lambda e: (e[1], -e[2], e[0] != ""))
        for path, _, d in _leaves(mine):
            all_ += d
            if RETENTION in program_trace.scope_parts(path):
                hit += d
    return 100.0 * hit / all_ if hit else None
