"""Milliseconds of an engine step in which the host worked and the device
did not: host clock around ``engine.step()`` (the ``bench.engine_step``
spans of the traced slice) less device busy time, per step."""

from benchmark.lib import trace


def read(record):
    lo, hi = record.window
    steps = [e for e in record.trace["host"]
             if e[0] == "bench.engine_step" and lo <= e[1] and e[1] + e[2] <= hi]
    if not steps:
        return None
    host_ns = sum(e[2] for e in steps)
    busy_ns = trace.busy_seconds(record.trace, steps[0][1],
                                 steps[-1][1] + steps[-1][2]) * 1e9
    return (host_ns - busy_ns) / len(steps) / 1e6
