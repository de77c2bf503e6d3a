"""Percent of the device time of ``serve_decode``'s operations, in the
traced slice, spent in the Pallas kernel ``ssm_state_step`` (the one-token
step of every decoding slot's recurrent state, advanced in the state pool
and read out for ``y`` in one pass; one call a state layer): the
operations whose scope path holds the kernel's ``name=``
(``jit(serve_decode)/ssm/state/jit(_impl)/ssm_state_step/pallas_call``).

The kernel's name in the trace is the counter that says the mechanism
engaged: a program that slices the layer out of the pool, advances it
and writes it back through a select (the parent; a model outside the
kernel's predicate) has no such operation, and the reader then returns
nothing."""

from benchmark.lib import scope_time

KERNEL = ("ssm_state_step",)


def read(record):
    return scope_time.share(record, KERNEL, "serve_decode")
