"""The flash kernels' share of the chip's bf16 peak: the causal attention
operations of one training step, from shapes, over the peak times the
device time of ``flash_fwd``, ``flash_bwd_dkv`` and ``flash_bwd_dq`` in
one ``lm_train_step`` execution (mean over the traced slice).

Operations: ``attn = 4 * hidden * seq_len * layers`` a token is the
forward pass over the full L x L square (the term of that name in
``shapes.lm_train_flops_per_token``, which ``mfu`` counts whole);
training costs three forward passes, and a causal kernel need not touch
the masked half, so a step needs ``3 * attn / 2`` a token: the share
cannot pass 100% by skipping what the mask removes. A forward kernel run
again for the backward pass adds time and no operations."""

from benchmark.lib import program_trace, shapes, trace

KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def kernel_of(name: str):
    """The Pallas kernel an "XLA Ops" event runs, by the instruction
    name the kernel's ``name=`` gave it (``%flash_fwd.3 = ...``)."""
    if not trace.is_kernel(name):
        return None
    stem = trace.short_name(name).split(":")[0]
    return stem if stem in KERNELS else None


def read(record):
    lo, hi = record.window
    steps = program_trace.runs(program_trace.of(record), "lm_train_step",
                               lo, hi)
    n = sum(len(v) for v in steps.values())
    kernel_ns = 0
    for dev, ivs in steps.items():
        ops = [(s, s + d) for name, s, d in
               record.trace["devices"][dev]["ops"] if kernel_of(name)]
        kernel_ns += trace.total(o for s, e in ivs
                                 for o in trace.clip(ops, s, e))
    if not n or not kernel_ns:
        return None
    cfg, L = record.config, record.traffic["seq_len"]
    attn = 4 * cfg["hidden_size"] * L * cfg["num_hidden_layers"]
    per_step = 3 * attn / 2 * L * record.traffic["microbatch"]
    peak_flops, _ = shapes.peak(record.device["kind"])
    return 100.0 * per_step / (peak_flops * kernel_ns / n / 1e9)
