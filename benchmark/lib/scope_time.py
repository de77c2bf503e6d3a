"""Device time of one program's operations by scope, in absolute terms
(``program_trace.scope_share`` gives shares only), and what each decode
step of a model with recurrent state had to read. Everything here returns
nothing on a trace that lacks what it reads (a program without the
scopes, an engine without the counts: the parent's, a dense cell's).

Two things the hybrid cell taught (PR 33, PERF.md section 7):

- XLA:TPU runs ``lax.ragged_dot`` as a custom call of its own whose
  operations carry the ``op_name`` ``ragged-dot-none`` (and a small
  ``ragged-dot-metadata``) and NO ``named_scope`` path, so the expert
  layer's two grouped products would count under no scope at all.
  ``MOE`` therefore names them beside the scope ``moe``: the program has
  no other grouped product.
- ``ServeEngine.step()`` annotates 24 steps of every 240
  (``profiling.burst``), and this cell runs about 20 steps a second: a
  5 s slice holds a burst less than half the time. So the counts of the
  ``tpu_ddp.serve.decode`` spans are used where the slice has such spans,
  and the window's means (the engine's gauges ``serve_decode_rows`` /
  ``serve_decode_context_tokens``, which the runner turns into the
  counters ``decode_rows_mean`` / ``decode_context_tokens_mean``)
  where it has none.
"""

from __future__ import annotations

from benchmark.lib import program_trace

SSM = ("ssm",)
MOE = ("moe", "ragged-dot")
MOE_AND_SHARED = MOE + ("shared_mlp",)
DECODE_SPAN = "tpu_ddp.serve.decode"


def _under(path: str, names) -> bool:
    """Is an operation's ``op_name`` path under one of ``names``: a
    component of its scope path, or the start of a path without scopes
    (an XLA custom call named after what it computes)."""
    return any(p in names for p in program_trace.scope_parts(path)) \
        or ("/" not in path and any(path.startswith(n) for n in names))


def per_run_ms(record, names, program: str):
    """``(under, all)``: mean device milliseconds per execution of
    ``program`` in the traced slice, of its operations under ``names``
    and of all its operations. ``None`` where none is under ``names``."""
    prog = program_trace.of(record)
    within = program_trace.runs(prog, program, *record.window)
    hit = all_ = runs = 0
    for dev, keep in within.items():
        runs += len(keep)
        j = 0
        for path, s, d in prog["scopes"].get(dev, []):
            while j < len(keep) and keep[j][1] <= s:
                j += 1
            if j == len(keep):
                break
            if s < keep[j][0] or s + d > keep[j][1]:
                continue
            all_ += d
            if _under(path, names):
                hit += d
    if not hit:
        return None
    return hit / runs / 1e6, all_ / runs / 1e6


def share(record, names, program: str):
    """Percent of ``program``'s operations' device time under ``names``."""
    ms = per_run_ms(record, names, program)
    return 100.0 * ms[0] / ms[1] if ms else None


def decode_steps(record) -> list:
    """``[(device_ms, state_slots, context_tokens), ...]``: what the
    traced decode steps of a model with recurrent state had to read, and
    how long each took. One entry for each annotated step of the slice,
    its span paired with the one execution of ``serve_decode`` that
    starts inside it (as ``decode_program_hbm_util`` pairs them); where
    the slice holds no such span, one entry of the window's means."""
    prog = program_trace.of(record)
    lo, hi = record.window
    per = [iv for ivs in
           program_trace.runs(prog, "serve_decode", lo, hi).values()
           for iv in ivs]
    out = []
    for _, start, dur, counts in program_trace.spans_in(
            prog, DECODE_SPAN, lo, hi):
        mine = [e - s for s, e in per if start <= s < start + dur]
        if len(mine) == 1 and counts.get("state_slots"):
            out.append((mine[0] / 1e6, counts["state_slots"],
                        counts["context_tokens"]))
    rows = record.counters.get("decode_rows_mean")
    if not out and per and rows and "layer_types" in record.config:
        out.append((sum(e - s for s, e in per) / len(per) / 1e6, rows,
                    record.counters["decode_context_tokens_mean"]))
    return out
