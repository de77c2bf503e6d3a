"""Reduction of a JAX profiler trace to the numbers the benchmark prints.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
:func:`load_xplane` turns it into a plain dict (kept small enough to
store as JSON, which is how the recorded trace under
``tests/benchmark/`` is kept), and every other function here works on
that dict and on nothing else:

    {"devices": {"/device:TPU:0": {"ops":   [[name, start_ns, dur_ns], ...],
                                   "async": [[name, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...]}

``ops`` is the plane's "XLA Ops" line: what the TensorCore executed, one
event per HLO instruction, named by the instruction's text. ``async`` is
its "Async XLA Ops" line (copy-start .. copy-done, asynchronous
collectives). ``host`` holds the benchmark's own ``bench.*`` spans
(``jax.profiler.TraceAnnotation``), which the profiler writes on the
same clock.

Intervals are half-open ``(start, end)`` pairs in nanoseconds.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")
# "%fusion.11 = (f32[3072,49152]{1,0:T(8,128)}, f32[...]) fusion(...), kind=kLoop"
_NAME = re.compile(r"^%?([^\s=]+) = ")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[^\]]*\]")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def load_xplane(trace_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``trace_dir`` as the dict above."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            out["devices"][plane.name] = {
                key: [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("async", "Async XLA Ops"))}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    out["host"].sort(key=lambda e: e[1])
    return out


# ---- interval arithmetic ---------------------------------------------------

def union(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    merged: list = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals) -> int:
    return sum(end - start for start, end in intervals)


def clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def _spans(events) -> list:
    return [(s, s + d) for _, s, d in events]


def _inside(start: int, dur: int, lo: int, hi: int) -> int:
    """Nanoseconds of the event that lie in ``[lo, hi)``."""
    return max(0, min(start + dur, hi) - max(start, lo))


# ---- what an event is ------------------------------------------------------

def opcode(name: str) -> str:
    m = _OPCODE.search(name)
    return m.group(1) if m else name.split("(")[0].lstrip("%")


def is_collective(name: str) -> bool:
    """By the opcode, or by the instruction's own name where XLA:TPU
    wraps the collective in a generic ``async-start`` / ``async-done``
    (``%all-reduce-start.3 = ... async-start(...)``)."""
    m = _NAME.match(name)
    stem = re.sub(r"[.\d]+$", "", m.group(1)) if m else ""
    return any(op.removesuffix("-start").removesuffix("-done")
               in COLLECTIVE_OPCODES for op in (opcode(name), stem))


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel: the custom call XLA:TPU compiles it to."""
    return 'custom_call_target="tpu_custom_call"' in name


def short_name(name: str) -> str:
    """``fusion:f32[3072,49152]``: the instruction's name without its
    serial number, and the (first) shape it writes. Instances of one
    fusion in different layers then fall under one label."""
    m = _NAME.match(name)
    if not m:
        return name[:60]
    shape = _SHAPE.search(name, m.end())
    base = re.sub(r"[.\d]+$", "", m.group(1))
    return f"{base}:{shape.group(0) if shape else ''}"


# ---- reductions ------------------------------------------------------------

def span_window(trace: dict, span: str):
    """From the start of the first ``span`` to the end of the last."""
    hits = [e for e in trace["host"] if e[0] == span]
    if not hits:
        return None
    return hits[0][1], max(s + d for _, s, d in hits)


def busy(trace: dict, lo: int, hi: int) -> dict:
    """Per device, the disjoint intervals of ``[lo, hi)`` in which an
    operation ran."""
    return {dev: clip(union(_spans(d["ops"])), lo, hi)
            for dev, d in trace["devices"].items()}


def busy_seconds(trace: dict, lo: int, hi: int) -> float:
    """Seconds an operation ran, averaged over the devices."""
    per = [total(iv) for iv in busy(trace, lo, hi).values()]
    return sum(per) / len(per) / 1e9


def idle_share(trace: dict, lo: int, hi: int) -> float:
    return 100.0 * (1.0 - busy_seconds(trace, lo, hi) * 1e9 / (hi - lo))


def share_of_busy(trace: dict, lo: int, hi: int, pred) -> float:
    """Percent of device busy time spent in events ``pred`` accepts."""
    hit = all_ = 0
    for d in trace["devices"].values():
        for name, s, dur in d["ops"]:
            part = _inside(s, dur, lo, hi)
            all_ += part
            if pred(name):
                hit += part
    return 100.0 * hit / all_ if all_ else 0.0


def collective_exposed_seconds(trace: dict, lo: int, hi: int) -> float:
    """Seconds, averaged over devices, in which a collective was in
    flight on a device and nothing else ran there."""
    per = []
    for d in trace["devices"].values():
        coll = union(_spans(e for e in d["ops"] + d["async"]
                            if is_collective(e[0])))
        work = union(_spans(e for e in d["ops"]
                            if not is_collective(e[0])))
        per.append(total(clip(subtract(coll, work), lo, hi)))
    return sum(per) / len(per) / 1e9


def top_ops(trace: dict, lo: int, hi: int, n: int = 10) -> list:
    """``[[label x count, seconds], ...]``: device operations by total
    time over all devices, largest first."""
    secs: dict = {}
    count: dict = {}
    for d in trace["devices"].values():
        for name, s, dur in d["ops"]:
            part = _inside(s, dur, lo, hi)
            if part:
                key = short_name(name)
                secs[key] = secs.get(key, 0) + part
                count[key] = count.get(key, 0) + 1
    order = sorted(secs, key=secs.get, reverse=True)[:n]
    return [[f"{k} x{count[k]}", secs[k] / 1e9] for k in order]


def innermost(spans) -> list:
    """Nested ``[name, start, dur]`` spans as disjoint, sorted
    ``(start, end, name)`` segments, each named by the innermost span
    that covers it."""
    out: list = []
    stack: list = []            # (name, end) of the spans now open
    at = 0

    def emit(lo, hi, name):
        if hi > lo:
            out.append((lo, hi, name))

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            inner, end = stack.pop()
            emit(at, end, inner)
            at = max(at, end)
        if stack:
            emit(at, start, stack[-1][0])
        stack.append((name, start + dur))
        at = start
    while stack:
        inner, end = stack.pop()
        emit(at, end, inner)
        at = max(at, end)
    return out


def idle_gaps(trace: dict, lo: int, hi: int, n: int = 10) -> list:
    """``[[span, seconds], ...]``: time in which NO device ran anything,
    by the innermost benchmark span the host was in, largest first."""
    all_busy = union(iv for ivs in busy(trace, lo, hi).values()
                     for iv in ivs)
    gaps = subtract([(lo, hi)], all_busy)
    by_span = {"(no span)": total(gaps)}
    segments, j = innermost(trace["host"]), 0
    for g_lo, g_hi in gaps:
        while j < len(segments) and segments[j][1] <= g_lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g_hi:
            part = min(g_hi, segments[k][1]) - max(g_lo, segments[k][0])
            by_span[segments[k][2]] = by_span.get(segments[k][2], 0) + part
            by_span["(no span)"] -= part
            k += 1
    order = sorted((k for k, v in by_span.items() if v > 0),
                   key=by_span.get, reverse=True)[:n]
    return [[k, by_span[k] / 1e9] for k in order]
