"""Retrace sentinel: fail tests on unexpected recompiles.

The round-8 regression class: ``build_multi_step`` rebuilt its K-step
scan every epoch, so a "compiled" training loop silently re-lowered and
re-compiled the same program over and over — visible only as a
mysteriously slow wall clock. :func:`no_retrace` turns it into a hard
failure: it counts XLA compiles per callable name for the duration of
the block and raises :class:`RetraceError` if any watched callable
compiles more than ``max_compiles`` times.

Counting goes through jax's public instrumentation hook
(``jax.monitoring.register_event_duration_secs_listener``): every
request to the backend compiler records one
``/jax/core/compile/backend_compile_duration`` event carrying the
program's name (``jit(<callable>)``) and how long it took. A program
loaded from the persistent compilation cache records the event too
(with a short duration), so a warm cache never hides a retrace. The
same events give :attr:`_CompileCounter.compile_seconds`, the set-up
time a block paid — ``chip_smoke.py`` reports it apart from the
steady-state time.

Counting is by CALLABLE NAME, deliberately: the retrace bug class is
"the same function compiled twice with different shapes/avals", which
per-program keys would classify as two distinct programs and miss.
The cost is that jax's internal eager-op helper jits (``jit(multiply)``
etc., which legitimately compile per dtype/shape) must be ignored —
the default ignore set covers them, and ``watch=`` restricts counting
to exactly the names you mean to guard, which is the recommended form
inside training loops.

The pytest fixture (tests/conftest.py) exposes this as ``no_retrace``.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Lowering to MLIR and backend compilation, one event each per program.
# (The trace-duration events nest — an inner jitted function's trace
# lies inside its caller's — so they are left out of the sum.)
_SETUP_EVENTS = (_COMPILE_EVENT,
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")

# jax compiles these tiny helper programs for EAGER ops outside any
# user jit (one per dtype/shape combination) — they are not retraces
# of anything and must not trip the sentinel. Underscore-prefixed
# names (_reduce_sum, _threefry_split, ...) are ignored wholesale.
IGNORED_CALLABLES = frozenset({
    "convert_element_type", "broadcast_in_dim", "multiply", "add",
    "subtract", "divide", "true_divide", "floor_divide", "remainder",
    "power", "negative", "iota", "concatenate", "reshape", "transpose",
    "squeeze", "expand_dims", "copy", "select_n", "where", "clip",
    "equal", "not_equal", "less", "less_equal", "greater",
    "greater_equal", "maximum", "minimum", "abs", "sign", "exp", "log",
    "sqrt", "rsqrt", "tanh", "fn", "stack", "split", "full", "ones",
    "zeros", "arange", "take", "gather", "dynamic_slice",
    "dynamic_update_slice", "cumsum", "argmax", "argmin", "sort",
    "isnan", "isfinite", "logical_and", "logical_or", "logical_not",
    "bitcast_convert_type", "device_put", "ravel", "squeeze",
})


class RetraceError(AssertionError):
    """A watched callable compiled more often than allowed."""


class _CompileCounter:
    """Listener for jax's compile-path duration events."""

    def __init__(self, watch, ignore):
        self.watch = tuple(watch) if watch is not None else None
        self.ignore = ignore
        self.counts: dict = {}
        self.compile_seconds = 0.0

    def __call__(self, event, duration, fun_name="?", **_):
        if event in _SETUP_EVENTS:
            self.compile_seconds += duration
        if event != _COMPILE_EVENT:
            return
        name = str(fun_name)
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        if self.watch is not None:
            if name not in self.watch:
                return
        elif name in self.ignore or name.startswith("_"):
            return
        self.counts[name] = self.counts.get(name, 0) + 1


@contextmanager
def count_compiles(watch=None, ignore=IGNORED_CALLABLES):
    """Yield a live :class:`_CompileCounter` for the block: ``.counts``
    maps callable name -> compiles so far (``watch`` restricts counting
    to the given names; without it every non-helper compile counts) and
    ``.compile_seconds`` sums lower + compile time of every program the
    block built. No budget is enforced — that is :func:`no_retrace`."""
    counter = _CompileCounter(watch, ignore)
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        yield counter
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)


@contextmanager
def no_retrace(max_compiles: int = 1, watch=None,
               ignore=IGNORED_CALLABLES):
    """Context manager asserting bounded compiles per callable.

    ``max_compiles`` is the per-callable budget for the whole block
    (1 = "compile at most once"; use 0 for a block that must reuse
    existing executables only). ``watch`` and ``ignore`` are
    :func:`count_compiles`'s.

    Yields the live counter and raises :class:`RetraceError` on exit if
    any callable exceeded the budget. The usual causes: an un-padded
    batch remainder, a Python-int axis that became a float, a fresh
    closure identity per epoch.
    """
    with count_compiles(watch, ignore) as counter:
        yield counter
    offenders = {n: c for n, c in counter.counts.items()
                 if c > max_compiles}
    if offenders:
        detail = "; ".join(f"{n!r} compiled {c}x"
                           for n, c in sorted(offenders.items()))
        raise RetraceError(
            f"unexpected recompilation (> {max_compiles} per "
            f"callable): {detail} — the round-8 bug class: a "
            "supposedly-compiled path is re-lowering every call")
