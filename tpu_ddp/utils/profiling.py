"""Tracing: the one module through which the program names what it does.

A trace is taken by opening a ``jax.profiler`` session:
:func:`profile_trace` (``TPU_DDP_PROFILE_DIR`` makes ``parts/common.py``
trace the ladder's first epoch with it) or ``benchmark/run.py --trace
1``. While a session is open the profiler records, on ONE clock,

- **host spans** (:func:`span`, a ``jax.profiler.TraceAnnotation``): what
  the host was doing, with the counts of that step as the event's stats;
- **programs**: each jitted program the device ran, under the name its
  Python function carries (:func:`program`), on the device plane's
  "XLA Modules" line as ``jit_<name>(<fingerprint>)``;
- **kernels**: each Pallas kernel, under the ``name=`` of its
  ``pallas_call``, which XLA:TPU makes the custom call's instruction name;
- **scopes** (``jax.named_scope``): the layer an operation belongs to, as
  the path in the "XLA Ops" events' ``tf_op`` stat
  (``jit(serve_decode)/attn/kv_gather/...``).

With no session open a span is a no-op and the names are only metadata
of the compiled programs: nothing here is switched on or off by a flag,
an environment variable or a config field.

The tables below are the single source of every name. ``docs/DESIGN.md``
("Tracing") prints them, ``benchmark/layer_metrics/`` reads them by name,
and ``tests/test_profiling.py`` holds the call sites to them both ways.

**Rules for a count** (a keyword of :func:`span`). It is a Python ``int``
or ``float`` that the step already has on the host. Never a device value:
no ``np.asarray``, ``.item()`` or ``float()`` of a ``jax.Array`` to fill
a count, which would be a sync. Nothing that costs more than O(slots) to
compute, because the argument is computed with tracing off too. A span's
counts are fixed when it opens.
"""

from __future__ import annotations

import contextlib
import os
import threading

import jax

PREFIX = "tpu_ddp."

# name -> (layer as in PERF.md section 3, what the span covers, counts)
SPANS = {
    "tpu_ddp.serve.step": (
        "serving", "all of ServeEngine.step() but its tally", ()),
    "tpu_ddp.serve.tally": (
        "serving", "a zero-width marker (mark) before serve.step at the "
        "first step after every TALLY_S of host clock, burst or not: the "
        "engine's running totals since it was built, out of its "
        "MetricsLogger (GAUGES, serve_decode_ahead, serve_decode_dry), "
        "each summed over the steps, decode steps or chunks it names, "
        "and the pool's usable blocks; two tallies differ by the sums "
        "over every step between them",
        ("steps", "decode_steps", "decode_ahead", "dry_steps",
         "decode_rows", "context_tokens", "prefill_chunks",
         "prefill_tokens", "prefill_sparse_kernel_chunks",
         "prefill_state_carried",
         "kv_blocks_in_use", "kv_blocks_usable",
         "host_busy_ms", "fetch_wait_ms", "queue_depth",
         # 0 but for a model with sparse-attention layers
         "selected_tokens", "index_scored_tokens")),
    "tpu_ddp.serve.schedule": (
        "serving", "chaos / subscriber hooks, deadline shedding, "
        "sched.admit(), and each pick of a prefill slot or of the decode "
        "slots", ()),
    "tpu_ddp.serve.admit": (
        "serving", "one marker per admitted request, inside schedule",
        ("rid", "waited_ms", "prompt_tokens", "cached_tokens")),
    "tpu_ddp.serve.prefill": (
        "serving", "one prefill chunk: build, upload, dispatch, "
        "pool.commit; the final chunk's first token stays on the device "
        "and is read with the step's decode rows. state_reset: 1 on a "
        "request's first chunk of a model that keeps recurrent state "
        "(the slot's state starts from zero), else 0. state_carried: 1 "
        "on a later chunk of such a model (it starts from the state the "
        "slot carries), else 0. "
        "index_scored_tokens: the positions a sparse-attention model's "
        "indexer scores as live, p + 1 summed over the chunk's prompt "
        "positions p (0 for any other model). sparse_kernel: 1 where "
        "the chunk's sparse-attention layers attend through the "
        "sparse_prefill_attn kernel, else 0",
        ("rid", "tokens", "start", "final", "state_reset",
         "state_carried", "index_scored_tokens", "sparse_kernel")),
    "tpu_ddp.serve.decode": (
        "serving", "one whole-bank decode step (plain or fused "
        "speculative): its dispatch and, in the plain engine, the "
        "harvest of the step BEFORE it. ahead: 1 where that step's "
        "decode rows were still unread at the dispatch, 0 where the "
        "engine was at rest (at spec_k == 0 also the counters "
        "serve_decode_ahead / serve_decode_at_rest). dry, only where "
        "ahead: 1 where that step's samples were already on the device "
        "(is_ready) before the step dispatched anything (its chunk, if "
        "it has one, goes first), so the device had run out of work "
        "before the host gave it more; 0 where they were not (also the "
        "counter serve_decode_dry). state_slots: the slots whose "
        "recurrent state the step advances (its rows, for a model that "
        "keeps such state; else 0). selected_tokens: the positions a "
        "sparse-attention model's attention reads, min(length + 1, "
        "index_topk) summed over the rows (0 for any other model)",
        ("context_tokens", "ahead", "dry", "state_slots",
         "selected_tokens")),
    "tpu_ddp.serve.decode.tables": (
        "serving", "ensure_blocks, tier residency, the numpy tables "
        "and vectors", ()),
    "tpu_ddp.serve.decode.dispatch": (
        "serving", "jnp.asarray uploads, the jitted call(s), pool.commit",
        ()),
    "tpu_ddp.serve.decode.fetch": (
        "serving", "the blocking np.asarray of tokens, log-probabilities "
        "and flags: in the plain engine those of the step before, while "
        "this step's programs are queued behind it (directly under "
        "serve.step in a step that dispatches no decode). The first "
        "tokens of that step's final chunks come first, in a fetch / "
        "emit pair of their own, and do not wait for the decode rows",
        ()),
    "tpu_ddp.serve.decode.emit": (
        "serving", "the per-slot loop over what fetch read: quarantine, "
        "length += 1, _emit (stamps, callbacks, retire)", ()),
    "tpu_ddp.lm.put_batch": (
        "train loop", "LMTrainer / PipelineLMTrainer.put_batch: host "
        "arrays to sharded device arrays", ("tokens",)),
    "tpu_ddp.lm.train_step": (
        "train loop", "the dispatch of one jitted LM step (not the wait "
        "for its loss: that is the caller's). dry, from the second step "
        "of a trainer on: 1 where the step before's loss was already on "
        "the device (is_ready) as the span opened, so the device had run "
        "out of work before the host gave it more; else 0",
        ("step", "dry")),
    "tpu_ddp.train.data_next": (
        "host data path", "next() of the batch stream train_epoch "
        "iterates", ()),
    "tpu_ddp.train.put_batch": (
        "train loop", "Trainer.put_batch(es) inside train_epoch", ()),
    "tpu_ddp.train.dispatch": (
        "train loop", "train_step_async, or one K-step group",
        ("it", "step")),
    "tpu_ddp.train.harvest": (
        "train loop", "a harvested step: loss fetch, guard, heartbeat, "
        "checkpoint / invariant / publish cadences", ("it",)),
}

# Jitted programs: the __name__ of the function handed to jax.jit, so
# "jit_<name>" in HLO, in jax.monitoring's compile events
# (analysis/retrace.py) and on the trace's "XLA Modules" line.
SERVE_DECODE = "serve_decode"
SERVE_PREFILL = "serve_prefill"
SERVE_FEED = "serve_feed"
SERVE_SPEC = "serve_spec"
SERVE_DECODE_TIERED = "serve_decode_tiered"
SERVE_PREFILL_TIERED = "serve_prefill_tiered"
SERVE_PREFILL_CP = "serve_prefill_cp"
SERVE_ADOPT_DECODE = "serve_adopt_decode"
LM_TRAIN_STEP = "lm_train_step"
LM_TRAIN_MULTI_STEP = "lm_train_multi_step"
DDP_TRAIN_STEP = "ddp_train_step"
DDP_TRAIN_MULTI_STEP = "ddp_train_multi_step"
DDP_EVAL_STEP = "ddp_eval_step"

PROGRAMS = {
    SERVE_DECODE: "serve/engine.py: one token for the whole slot bank",
    SERVE_PREFILL: "serve/engine.py: one prefill chunk of one prompt",
    SERVE_FEED: "serve/engine.py: each slot's pending token for the "
                "decode step, taken from the unread samples on the "
                "device where the host does not have it yet",
    SERVE_SPEC: "serve/speculative.py: fused draft + verify",
    SERVE_DECODE_TIERED: "serve/long_context.py: decode over hot + cold "
                         "K/V tiers",
    SERVE_PREFILL_TIERED: "serve/long_context.py: prefill chunk over "
                          "hot + cold K/V tiers",
    SERVE_PREFILL_CP: "serve/long_context.py: context-parallel prefill "
                      "chunk",
    SERVE_ADOPT_DECODE: "fleet/disagg.py: K/V block adoption fused with "
                        "the decode step",
    LM_TRAIN_STEP: "train/lm.py: one LMTrainer / PipelineLMTrainer step",
    LM_TRAIN_MULTI_STEP: "train/lm.py: K LM steps scanned in one "
                         "dispatch",
    DDP_TRAIN_STEP: "train/engine.py: one Trainer step (every sync rung)",
    DDP_TRAIN_MULTI_STEP: "train/engine.py: K Trainer steps scanned in "
                          "one dispatch",
    DDP_EVAL_STEP: "train/engine.py: one evaluation batch",
}

# Pallas kernels: the name= of each pl.pallas_call in ops/pallas/.
KERNELS = {
    "flash_fwd": "flash_attention.py: forward, with the log-sum-exp",
    "flash_bwd_dkv": "flash_attention.py: backward, dK and dV",
    "flash_bwd_dq": "flash_attention.py: backward, dQ",
    "quant_matmul": "quant_matmul.py: int8 x int8 -> f32 with scales",
    "fused_sgd": "sgd.py: momentum SGD update in place",
    "bn_relu_stats": "bn_relu.py: per-channel sum and sum of squares",
    "bn_relu_apply": "bn_relu.py: normalise, scale, ReLU",
    "bn_relu_bwd_reduce": "bn_relu.py: backward reductions",
    "bn_relu_bwd_dx": "bn_relu.py: backward dx",
    "paged_decode_attn": "paged_attention.py: one token per slot against "
                         "the K/V pool read in place, one call a layer",
    "ssm_state_step": "ssm_state_step.py: one token per slot of a "
                      "state-space layer, the recurrent state advanced in "
                      "the state pool and y read out of the tile held, one "
                      "call a state layer",
    "grouped_matmul": "grouped_matmul.py: the dropless expert layer's "
                      "grouped product over the sorted assignments, each "
                      "held expert's matrix streamed once past rows "
                      "resident on chip, two calls an expert layer",
    "sparse_prefill_attn": "sparse_prefill_attention.py: a prefill "
                           "chunk's queries over the gathered K/V with "
                           "the indexer's selection as the mask, one "
                           "flash-style pass, one call a sparse-attention "
                           "layer",
}

# jax.named_scope at the layer boundaries of the programs the cells run.
# Metadata only: a scope changes no compiled code.
SCOPES = {
    "embed": "token embedding lookup",
    "attn": "a block's attention half: LayerNorm, QKV, attention, "
            "output projection, residual",
    "kv_write": "inside attn, serving: the new K/V scattered into the "
                "paged pool",
    "kv_gather": "inside attn, serving: the pool gathered through the "
                 "block tables into a contiguous view. serve_prefill "
                 "always; serve_decode and serve_adopt_decode only "
                 "where the paged_decode_attn kernel does not take the "
                 "shapes (they then have no such scope). serve_spec and "
                 "the tiered and context-parallel programs gather "
                 "without the scope",
    "mlp": "a block's MLP half: norm, MLP, residual",
    "ssm": "a state-space block's mixer half: norm, projections, "
           "convolution, the recurrence (one token a slot in "
           "serve_decode, the chunked scan in serve_prefill), gated "
           "norm, output projection, residual",
    "state": "inside ssm, serving: the reads and writes of the state "
             "pool (the path ssm/state); in serve_decode the "
             "ssm_state_step kernel where it takes the pool. Inside "
             "retention, serve_decode: the slots' S and z read from the "
             "pool, the readout from them, the update written back (the "
             "path retention/state)",
    "retention": "a power-retention block's mixer half: norm, q / k / v "
                 "projections with their norms and RoPE, the gate, the "
                 "retention, output projection, residual",
    "carry": "inside retention, serve_prefill: what reads or makes the "
             "state, the slot's incoming state read, phi(Q)^T S0 and "
             "the folded state written (the path retention/carry)",
    "chunk": "inside retention, serve_prefill: the quadratic part "
             "within the chunk, (q.k)^2 weights and their sums (the path "
             "retention/chunk)",
    "moe": "inside mlp: the dropless expert layer over the experts "
           "held",
    "route": "inside moe: router, top-k, gates, and the sort of the "
             "assignments by expert (the path moe/route)",
    "experts": "inside moe: the gather of the sorted rows, the two "
               "grouped products and the weighted sum back to tokens "
               "(the path moe/experts). Each grouped product is the "
               "grouped_matmul kernel where it takes the shapes, under "
               "this path like any operation; elsewhere lax.ragged_dot, "
               "which XLA:TPU runs as a custom call of its own whose "
               "operations are named ragged-dot-none and carry NO scope "
               "path: a reader counts those by that name",
    "shared_mlp": "inside mlp: the shared gated MLP, every token",
    "index": "inside attn, serving, a sparse-attention layer: the "
             "indexer's projections, the scores of every position of "
             "the slot's table and the selection (the path attn/index; "
             "in serve_prefill inside the loop over the chunk's parts)",
    "sparse": "inside attn, serve_decode, a sparse-attention layer: the "
              "gather of the selected positions' K/V rows and attention "
              "over them (the path attn/sparse)",
    "head": "final LayerNorm and output head",
    "sample": "serving: token sampling and the non-finite check",
    "loss": "trainers: cross-entropy on the logits",
    "grad_sync": "trainers: gradient mean over the data axes",
    "clip": "trainers: global-norm gradient clipping",
    "optimizer": "trainers: the optimizer update",
}


# Gauges the serving engine keeps in its ``MetricsLogger`` whether or not
# a trace is being taken (``metrics.gauges[name]``: count, total, max,
# last). A ``serve.tally`` puts their running counts and totals into the
# trace, for EVERY step where the spans are a burst's only (below).
GAUGES = {
    "serve_kv_pool_bytes": "set once: what the paged K/V pool holds on "
                           "the device, its index-key pages included",
    "serve_state_pool_bytes": "set once: what the state pool holds (0 "
                              "for a model without recurrent state)",
    "serve_decode_rows": "every decode step: its rows (for a model with "
                         "recurrent state also its state_slots)",
    "serve_decode_context_tokens": "every decode step: the K/V "
                                   "positions it reads (the span's "
                                   "context_tokens)",
    "serve_prefill_tokens": "every prefill chunk: its prompt tokens "
                            "(the span's tokens)",
    "serve_prefill_state_carried": "every prefill chunk of a model with "
                                   "recurrent state: 1 where it starts "
                                   "from the state the slot carries (the "
                                   "span's state_carried)",
    "serve_prefill_sparse_kernel_chunks": "every prefill chunk of a "
                                          "model with sparse-attention "
                                          "layers: 1 where they attend "
                                          "through the kernel (the "
                                          "span's sparse_kernel)",
    "serve_decode_selected_tokens": "every decode step of a model with "
                                    "sparse-attention layers: the "
                                    "positions attention reads (the "
                                    "span's selected_tokens)",
    "serve_decode_index_tokens": "every decode step of a model with "
                                 "sparse-attention layers: the live "
                                 "positions the indexer scores, "
                                 "context_tokens plus the rows",
    "serve_queue_depth": "every step: the requests waiting for a slot",
    "serve_slot_occupancy": "every step: the share of slots held",
    "serve_kv_blocks_in_use": "every step: the K/V pool's blocks held",
    "serve_host_busy_ms": "every step: its wall time less what it spent "
                          "blocked in serve.decode.fetch, the host's own "
                          "work",
    "serve_fetch_wait_ms": "every step: what it spent blocked in "
                           "serve.decode.fetch",
    "serve_ttft_ms": "every request's first token: milliseconds since "
                     "it was submitted",
}

# A ``serve.tally`` marker goes into the trace at the first engine step
# after every TALLY_S seconds of host clock.
TALLY_S = 0.5


# ``ServeEngine.step`` annotates its steps in bursts: the first
# BURST_STEPS of every BURST_EVERY, whole steps or nothing (see
# :func:`burst`). Every mean a reader takes "per step" is then a mean
# over the annotated steps.
BURST_STEPS = 24
BURST_EVERY = 240

_thread = threading.local()     # .quiet: this thread's spans are dropped


def mark(name: str, counts) -> None:
    """A zero-width span ``name`` with the counts ``counts()`` returns,
    recorded whether or not :func:`burst` left the step out. Costs one
    check while no profiler session is open: ``counts`` is not called."""
    if jax.profiler.TraceAnnotation.is_enabled():
        with jax.profiler.TraceAnnotation(name, **counts()):
            pass


def span(name: str, **counts):
    """A host span on the profiler's clock, with ``counts`` as the
    event's stats (``jax.profiler.ProfileData`` gives them back as
    ``event.stats``). ``name`` is a key of :data:`SPANS`, written as a
    literal at the call site. A no-op while no profiler session is open,
    and inside a step that :func:`burst` left out."""
    if getattr(_thread, "quiet", False):
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **counts)


@contextlib.contextmanager
def burst(n: int):
    """Around iteration ``n`` (1, 2, ...) of a loop that turns over many
    times a second: the spans this thread opens inside are recorded only
    in the first :data:`BURST_STEPS` iterations of every
    :data:`BURST_EVERY`.

    Why: a trace's readers pair host spans with device operations, and
    the serving engine on the chip runs some 66 steps a second of some
    2,000 device operations each (PR 30), 4.5 times what it ran when the
    spans went in. Consecutive whole steps, not one in five: the spans of
    one request (``admit``, its ``prefill`` chunks, the ``decode`` steps
    after them) stay next to each other, and a step is either all there
    or absent, so a sum over spans divided by the ``serve.step`` spans
    found is still a mean per step."""
    before = getattr(_thread, "quiet", False)
    _thread.quiet = (n - 1) % BURST_EVERY >= BURST_STEPS
    try:
        yield
    finally:
        _thread.quiet = before


def spanned(iterable, name: str):
    """``iterable``, with each ``next()`` of it under ``span(name)``."""
    it = iter(iterable)
    while True:
        with span(name):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def program(name: str):
    """Decorator: give the function about to be jitted the program name
    ``name`` (a key of :data:`PROGRAMS`)."""
    if name not in PROGRAMS:
        raise KeyError(f"{name!r} is not in profiling.PROGRAMS")

    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn

    return rename


@contextlib.contextmanager
def profile_trace(logdir: str | None = None):
    """Capture a trace (device operations and the spans above) into
    ``logdir`` for the duration of the ``with`` block; no-op when
    ``logdir`` is falsy."""
    if not logdir:
        yield
        return
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def profile_dir_from_env() -> str | None:
    return os.environ.get("TPU_DDP_PROFILE_DIR") or None
