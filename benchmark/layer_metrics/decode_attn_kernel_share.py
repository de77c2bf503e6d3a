"""Percent of ``serve_decode``'s device time spent in the Pallas kernel
``paged_decode_attn`` (attention of the one new token of every slot,
reading the K/V pool in place; one call a layer): the kernel's events on
the device's "XLA Ops" line that lie inside executions of the program,
over the executions' own time on "XLA Modules", in the traced slice.

The kernel's name in the trace is the counter that says the mechanism
engaged: a program that gathers the pool instead has no such event, and
the reader then returns nothing."""

from benchmark.lib import program_trace, trace

KERNEL = "paged_decode_attn"


def is_kernel(name: str) -> bool:
    """An "XLA Ops" event of the kernel, by the instruction name its
    ``name=`` gave it (``%paged_decode_attn.7 = ... custom-call(``)."""
    return trace.is_kernel(name) \
        and trace.short_name(name).split(":")[0] == KERNEL


def read(record):
    steps = program_trace.runs(program_trace.of(record), "serve_decode",
                               *record.window)
    kernel_ns = program_ns = 0
    for dev, ivs in steps.items():
        # One walk over both: executions and events are in time order.
        ivs, j = sorted(ivs), 0
        for name, s, d in sorted(record.trace["devices"][dev]["ops"],
                                 key=lambda e: e[1]):
            while j < len(ivs) and ivs[j][1] <= s:
                j += 1
            if j == len(ivs):
                break
            if is_kernel(name):
                lo, hi = ivs[j]
                kernel_ns += max(0, min(s + d, hi) - max(s, lo))
        program_ns += trace.total(ivs)
    if not kernel_ns:
        return None
    return 100.0 * kernel_ns / program_ns
