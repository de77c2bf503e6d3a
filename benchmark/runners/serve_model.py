"""Runner ``serve_model``: ``ServeEngine`` on one chip under a fixed
request set, closed loop (``clients``) or open loop (``rate_per_s``), for
whatever model family the configuration file names.

``runners/serve.py``'s window, drain, failure rules and counters, with
the model and its plain reference found by name: the configuration's
``family`` key (``gpt`` if it has none) names
``benchmark/lib/families/<family>.py``, whose ``build(config,
**overrides)`` gives the model and whose optional
``reference_args(config)`` gives what the reference takes beside the
parameters and the tokens, and ``benchmark/reference/<family>.py``, whose
``log_probs(params, tokens, **reference_args)`` is compared with the
engine's. A later configuration of a new family adds those two files and
no runner.

The configuration file's ``serve`` group gives the engine's geometry
(slots, ``max_seq_len``, block, ``prefill_chunk``); the traffic file
gives ``requests``, an explicit list of ``[prompt_len, output_len]``
pairs that every run serves, and how they arrive. ``--seed`` makes the
weights, the token ids, the order of the list and the arrival gaps.

Set-up serves the ``check`` requests alone and compares each reported
log-probability with the plain reference's full forward pass (which also
compiles both of the engine's programs), then offers the cell's load
until ``warm_completions`` requests have completed: the window opens on
an engine in steady state. In a closed loop the first request of each
client is cut to a staggered fraction of its output, so that the slots
do not all turn over together, as they would never do in service.

The window: tokens, and gaps between a request's tokens, count by the
stamp ``Request.token_times`` gives them, whichever request they belong
to. ``attempted`` is the requests due inside the window. After it closes
nothing new is sent and the engine is stepped on (the drain, outside
every metric) until each of them has its first token; what is then still
generating is cancelled. A request fails if it was shed, quarantined or
cancelled by the engine, got no first token, completed with another
number of tokens than asked for, or has a log-probability that is not
finite. One still generating at the close is attempted and, with its
first token in hand and no fault, not failed; its tokens after the close
are in no metric.
"""

from __future__ import annotations

import importlib
import math

import numpy as np


def _logprob_check(engine, params, check: dict, vocab: int, rng,
                   log_probs) -> dict:
    """Serve the check requests alone; largest difference between a
    log-probability the engine reported and the reference's for the same
    token after the same prefix."""
    import jax.numpy as jnp

    prompts = [rng.integers(0, vocab, size=n) for n in check["prompt_lens"]]
    handles = [engine.submit(p, check["new_tokens"], seed=i)
               for i, p in enumerate(prompts)]
    engine.run()
    worst = 0.0
    for prompt, h in zip(prompts, handles):
        if not h.done or len(h.tokens) != check["new_tokens"]:
            return {"max_abs_diff": math.inf, "why": "check request "
                    "did not complete"}
        full = np.concatenate([prompt, h.tokens]).astype(np.int32)
        ref = np.asarray(log_probs(params, jnp.asarray(full[:-1])))
        at = np.arange(len(prompt) - 1, len(full) - 1)
        want = ref[at, full[at + 1]]
        worst = max(worst, float(np.max(np.abs(want - h.logprobs))))
    return {"max_abs_diff": worst}


def run(bench) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.lib import loadgen
    from tpu_ddp.serve import ServeEngine

    traffic, config = bench.traffic, bench.config
    name = config.get("family", "gpt")
    family = importlib.import_module(f"benchmark.lib.families.{name}")
    reference = importlib.import_module(f"benchmark.reference.{name}")
    extra = getattr(family, "reference_args", lambda config: {})(config)
    geo = config["serve"]
    seed = bench.seed % (2 ** 31 - 1)
    rng = np.random.default_rng(seed)
    model = family.build(config, max_seq_len=geo["max_seq_len"],
                         param_dtype=jnp.dtype(geo["param_dtype"]))
    with jax.default_device(bench.devices[0]):
        params = jax.jit(model.init)(jax.random.key(seed))
        engine = ServeEngine(model, params, num_slots=geo["num_slots"],
                             block_size=geo["block_size"],
                             prefill_chunk=geo["prefill_chunk"])
    jax.block_until_ready(params)
    bench.phase("init")

    check = _logprob_check(
        engine, params, traffic["check"], model.vocab_size, rng,
        lambda p, tokens: reference.log_probs(p, tokens, **extra))
    bench.phase("check_and_compile")

    # The request list in the seed's order, the token ids and the
    # arrival gaps, each from a stream of its own.
    order_rng, token_rng, gap_rng = (np.random.default_rng([seed, k])
                                     for k in range(3))
    pairs = traffic["requests"]
    clients = traffic.get("clients", 0)

    def lengths():
        for i, (p, o) in enumerate(loadgen.order_requests(pairs, order_rng)):
            # Stagger the first fill: client i's first answer is cut to
            # (i + 1) / clients of its length.
            yield (p, max(1, o * (i + 1) // clients)) if i < clients \
                else (p, o)

    def submit(prompt_len, output_len):
        with bench.span("bench.submit"):
            return engine.submit(
                token_rng.integers(0, model.vocab_size, size=prompt_len),
                output_len)

    def step():
        steps[0] += 1
        with bench.span("bench.engine_step"):
            return engine.step()

    steps = [0]
    arrivals = None if clients else loadgen.poisson_arrivals(
        traffic["rate_per_s"], gap_rng)
    load = loadgen.Load(submit, step, lengths(), clients=clients,
                        arrivals=arrivals, tick=bench.tick)
    load.run(lambda l: l.completed() >= traffic["warm_completions"])
    bench.phase("warm_up")

    gauges = engine.metrics.gauges
    occupancy = dict(gauges["serve_slot_occupancy"])
    # what each decode step read, where the engine keeps count of it
    per_decode = {name: dict(gauges[name]) for name in
                  ("serve_decode_rows", "serve_decode_context_tokens")
                  if name in gauges}
    steps[0] = 0
    t_open = bench.open_window()
    load.run(lambda l: bench.elapsed() >= bench.seconds)
    t_close = bench.close_window()
    queue_at_close = len(engine.sched.queue)
    steps_in_window = steps[0]
    after = gauges["serve_slot_occupancy"]
    decode_means = {
        name.replace("serve_", "") + "_mean":
        (gauges[name]["total"] - was["total"])
        / max(gauges[name]["count"] - was["count"], 1)
        for name, was in per_decode.items()}
    slots_mean = ((after["total"] - occupancy["total"])
                  / max(after["count"] - occupancy["count"], 1)
                  * engine.num_slots)

    stats = loadgen.window_stats(load.sent, t_open, t_close)
    # The drain: no new load; step until each attempted request has its
    # first token (or the engine gave it up), then stop what remains.
    waiting = [h for _, _, h in stats["due"]]
    while any(not h.token_times and not (h.done or h.shed or h.cancelled
                                         or h.quarantined)
              for h in waiting):
        if not engine.step():
            break
    stats = loadgen.window_stats(load.sent, t_open, t_close)
    gave_up = {id(h) for h in waiting
               if h.shed or h.cancelled or h.quarantined}
    for _, _, h in load.sent:
        if not h.done:
            engine.cancel(h)

    failed = 0
    for _, _, h in stats["due"]:
        asked = h.max_new_tokens
        ok = (id(h) not in gave_up and h.token_times
              and all(map(math.isfinite, h.logprobs))
              and (not h.done or h.cancelled or len(h.tokens) == asked))
        failed += not ok
    checks = {
        "all_requests_ok": failed == 0 and stats["attempted"] > 0,
        "logprobs_agree": check["max_abs_diff"]
        <= traffic["check"]["logprob_tol"],
    }
    values = {"serve_tok_s": stats["tok_s"]}
    if stats["itl_ms"]:
        values["itl_p95_ms"] = loadgen.percentile(stats["itl_ms"], 95)
    if stats["ttft_ms"]:
        values["ttft_p50_ms"] = loadgen.percentile(stats["ttft_ms"], 50)
    counters = {
        "engine_steps": steps_in_window,
        "window_s": t_close - t_open, "slot_occupancy_mean": slots_mean,
        "itl_p50_ms": loadgen.percentile(stats["itl_ms"], 50)
        if stats["itl_ms"] else None,
        "ttft_p95_ms": loadgen.percentile(stats["ttft_ms"], 95)
        if stats["ttft_ms"] else None,
        "generator_lateness_p95_ms":
        loadgen.percentile(stats["lateness_ms"], 95)
        if stats["lateness_ms"] else None,
        "mean_context_tokens": float(np.mean(
            [p + o / 2 for p, o in pairs])),
        **decode_means,
        "tokens_in_window": stats["tokens"],
        "completed_in_window": sum(
            1 for _, _, h in load.sent
            if h.done and not h.cancelled and h.token_times
            and t_open <= h.token_times[-1] < t_close),
    }
    return {"correct": all(checks.values()),
            "attempted": stats["attempted"], "failed": failed,
            "values": values, "counters": counters,
            "notes": {"checks": checks, "logprob_check": check,
                      "itl_samples": len(stats["itl_ms"]),
                      "ttft_samples": len(stats["ttft_ms"]),
                      # a backlog that grows shows in both of these
                      "queue_at_close": queue_at_close,
                      "ttft_p50_ms_by_half": [
                          loadgen.percentile(half, 50) if half else None
                          for half in (stats["ttft_ms"][:len(stats["ttft_ms"]) // 2],
                                       stats["ttft_ms"][len(stats["ttft_ms"]) // 2:])]}}
