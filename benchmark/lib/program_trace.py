"""What the program says of itself in a profiler trace: its spans with
their counts, its programs, and the scope of each device operation.

``lib/trace.py`` keeps the benchmark's own ``bench.*`` spans and the
device's operations by their HLO text. This module reads, from the same
``*.xplane.pb`` (``run.py`` leaves it under ``.bench_trace/`` and it is
still there when the per-layer readers run), what
``tpu_ddp/utils/profiling.py`` put there, into a plain dict:

    {"spans":    [[name, start_ns, dur_ns, {count: value}], ...],
     "programs": {"/device:TPU:0": [[program, start_ns, dur_ns], ...]},
     "scopes":   {"/device:TPU:0": [[scope_path, start_ns, dur_ns], ...]}}

Where each comes from on libtpu 0.0.34 / JAX 0.9.0 (looked at by hand
in traces of ``sc2-serve-gen`` and ``sc2-train-s4k``, PR 26):

- ``spans``: plane ``/host:CPU``, every line (one per host thread; the
  engine and the trainer run on ``python3``), events whose name starts
  with ``tpu_ddp.``. The keyword counts of ``profiling.span`` are the
  event's own stats, which ``jax.profiler.ProfileData`` gives as
  ``event.stats``.
- ``programs``: plane ``/device:TPU:<n>``, line "XLA Modules", one event
  per execution, named ``jit_<name>(<fingerprint>)``; kept as ``<name>``.
  The program's name is the ``__name__`` of the function handed to
  ``jax.jit`` (``profiling.program``).
- ``scopes``: the same plane's "XLA Ops" line, one event per HLO
  instruction executed. The event's own stats are only its device
  offset and duration; the ``jax.named_scope`` path
  (``jit(serve_decode)/attn/kv_gather/gather``) is the ``tf_op`` stat of
  the event's *metadata*, which ``ProfileData`` does not show. So the
  metadata tables of the plane are read from the file's bytes by
  :func:`event_metadata`, a few lines of protobuf wire format (the
  ``lines`` field, nearly all of the file, is skipped unread), and joined
  to the events by name. An instruction without the stat has the path
  ``""``. A fusion carries the ``op_name`` of its root instruction.

Without a trace, or on a trace of a program that has none of this,
:func:`load` gives empty lists and each reader built on it returns
``None``. Intervals are half-open ``(start, end)`` pairs in nanoseconds,
as in ``lib/trace.py``, whose interval arithmetic is reused.
"""

from __future__ import annotations

import glob
import os
import re
from pathlib import Path

from benchmark.lib import trace

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
SPAN_PREFIX = "tpu_ddp."
STEP = "tpu_ddp.serve.step"
SCOPE_STATS = ("tf_op", "op_name")
_MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_LOADED: dict = {}


# ---- the file ---------------------------------------------------------------

def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, read by whoever wants it."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return val

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = varint()
        elif wire == 2:
            size = varint()
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def event_metadata(path: str, stat_names=SCOPE_STATS) -> dict:
    """``{plane name: {event name: value}}``: for each plane of the
    ``XSpace`` at ``path``, the first of ``stat_names`` that an event's
    metadata carries as a string. (``XSpace.planes`` = 1; ``XPlane``:
    ``name`` 2, ``event_metadata`` 4, ``stat_metadata`` 5;
    ``XEventMetadata``: ``name`` 2, ``display_name`` 4, ``stats`` 5;
    ``XStat``: ``metadata_id`` 1, ``str_value`` 5, ``ref_value`` 7, the
    latter an id in ``stat_metadata`` whose name is the string.)"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_name = "", [], {}
        for pnum, _, val in _fields(plane):
            if pnum == 2:
                name = bytes(val).decode()
            elif pnum in (4, 5):
                entry = next((v for n, _, v in _fields(val) if n == 2),
                             None)
                if entry is None:
                    continue
                if pnum == 4:
                    events.append(entry)
                else:
                    f_ = {n: v for n, _, v in _fields(entry)}
                    stat_name[f_.get(1, 0)] = bytes(f_.get(2, b"")).decode()
        wanted = {i: stat_names.index(n) for i, n in stat_name.items()
                  if n in stat_names}
        found: dict = {}
        for entry in events:
            names, best = [], None
            for n, _, v in _fields(entry):
                if n in (2, 4):
                    names.append(bytes(v).decode())
                elif n == 5:
                    stat = {k: x for k, _, x in _fields(v)}
                    rank = wanted.get(stat.get(1))
                    if rank is None or (best and best[0] <= rank):
                        continue
                    if 5 in stat:
                        best = (rank, bytes(stat[5]).decode())
                    elif 7 in stat:
                        best = (rank, stat_name.get(stat[7], ""))
            if best:
                for n in names:
                    found[n] = best[1]
        if found:
            out[name] = found
    return out


def newest_xplane(trace_dir) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def program_name(module: str) -> str:
    """``jit_serve_decode(17007165299273803297)`` -> ``serve_decode``."""
    m = _MODULE.match(module)
    return m.group(1) if m else module


def load(trace_dir=TRACE_DIR) -> dict:
    """The newest trace under ``trace_dir`` as the dict above, read once
    per process and file."""
    path = newest_xplane(trace_dir)
    if path is None:
        return {"spans": [], "programs": {}, "scopes": {}}
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = _load(path)
    return _LOADED[key]


def _load(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    meta = event_metadata(path)
    out: dict = {"spans": [], "programs": {}, "scopes": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            paths = meta.get(plane.name, {})
            if "XLA Modules" in lines:
                out["programs"][plane.name] = [
                    [program_name(e.name), int(e.start_ns),
                     int(e.duration_ns)]
                    for e in lines["XLA Modules"].events]
            if "XLA Ops" in lines:
                out["scopes"][plane.name] = [
                    [paths.get(e.name, ""), int(e.start_ns),
                     int(e.duration_ns)]
                    for e in lines["XLA Ops"].events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["spans"].extend(
                    [e.name, int(e.start_ns), int(e.duration_ns),
                     dict(e.stats)]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    out["spans"].sort(key=lambda e: e[1])
    return out


def of(record) -> dict:
    """The program's trace for a reader's ``record``: the one the record
    brings (the tests' recorded slices), else the file ``run.py`` wrote."""
    return getattr(record, "program", None) or load()


# ---- spans ------------------------------------------------------------------

def spans_in(prog: dict, name: str, lo: int, hi: int) -> list:
    """The spans called ``name`` that lie whole inside ``[lo, hi)``."""
    return [e for e in prog["spans"]
            if e[0] == name and lo <= e[1] and e[1] + e[2] <= hi]


def self_time(spans) -> dict:
    """``{name: ns}``: each span's duration less what its children
    cover, summed by name."""
    out: dict = {}
    for lo, hi, name in trace.innermost([e[:3] for e in spans]):
        out[name] = out.get(name, 0) + hi - lo
    return out


def idle_by_span(record, spans) -> dict:
    """``{name: ns}``: time of the traced slice in which NO device ran
    anything, by the innermost of ``spans`` the host was in."""
    lo, hi = record.window
    all_busy = trace.union(iv for ivs in
                           trace.busy(record.trace, lo, hi).values()
                           for iv in ivs)
    gaps = trace.subtract([(lo, hi)], all_busy)
    out: dict = {}
    for s_lo, s_hi, name in trace.innermost([e[:3] for e in spans]):
        part = trace.total(trace.clip(gaps, s_lo, s_hi))
        if part:
            out[name] = out.get(name, 0) + part
    return out


def engine_idle_ms(record, leaves) -> float | None:
    """Device-idle milliseconds per ``serve.step`` whose innermost
    program span is one of ``leaves`` (full names)."""
    prog = of(record)
    lo, hi = record.window
    steps = spans_in(prog, STEP, lo, hi)
    if not steps or not any(e[0] in leaves for e in prog["spans"]):
        return None
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    inside = [e for e in prog["spans"] if lo <= e[1] and e[1] + e[2] <= hi]
    idle = idle_by_span(record, inside)
    return sum(idle.get(n, 0) for n in leaves) / len(steps) / 1e6


# ---- programs and scopes ----------------------------------------------------

def runs(prog: dict, program: str, lo: int, hi: int) -> dict:
    """``{device: [(start, end), ...]}``: the executions of ``program``
    that lie whole inside ``[lo, hi)``."""
    out = {}
    for dev, events in prog["programs"].items():
        hits = [(s, s + d) for name, s, d in events
                if name == program and lo <= s and s + d <= hi]
        if hits:
            out[dev] = hits
    return out


def device_ms_per_run(record, program: str) -> float | None:
    per = [iv for ivs in runs(of(record), program, *record.window).values()
           for iv in ivs]
    return trace.total(per) / len(per) / 1e6 if per else None


def scope_share(record, scopes, program: str | None = None):
    """``(share, unscoped)`` in percent: of the device time of the
    operations of the traced slice (those inside executions of
    ``program``, if given), the part under a scope named in ``scopes``,
    and the part whose path has no scope at all. ``None`` where no
    operation of the slice is under one of ``scopes``."""
    prog = of(record)
    lo, hi = record.window
    within = runs(prog, program, lo, hi) if program else None
    hit = bare = all_ = 0
    for dev, events in prog["scopes"].items():
        if within is None:
            keep = [(lo, hi)]
        else:
            keep = within.get(dev, [])
        j = 0
        for path, s, d in events:
            while j < len(keep) and keep[j][1] <= s:
                j += 1
            if j == len(keep):
                break
            if s < keep[j][0] or s + d > keep[j][1]:
                continue
            parts = scope_parts(path)
            all_ += d
            if not parts:
                bare += d
            elif any(p in scopes for p in parts):
                hit += d
    if not hit:
        return None
    return 100.0 * hit / all_, 100.0 * bare / all_


def scope_parts(path: str) -> list:
    """The ``named_scope`` components of an ``op_name`` path: what lies
    between the ``jit(...)`` program and the primitive, transformations
    (``jvp(attn)``, ``transpose(jvp(mlp))``) unwrapped."""
    parts = path.split("/")[1:-1]
    out = []
    for p in parts:
        while "(" in p and p.endswith(")"):
            p = p[p.index("(") + 1:-1]
        out.append(p)
    return out
