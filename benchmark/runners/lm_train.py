"""Runner ``lm_train``: ``LMTrainer`` on a mesh of the cell's chips, a
new seeded batch through ``put_batch`` every step.

Traffic parameters: ``seq_len``, ``microbatch``, ``use_flash``,
``zipf_offset`` (token ids are drawn with probability proportional to
1 / (id + offset): text is skewed, and a skewed stream gives the loss
something to learn, so that "falling" is a real check), ``loss_tol``.

``train_throughput`` counts whole steps: tokens of the steps dispatched
in the window, over the time from the window's opening to the
``block_until_ready`` of the last one. The host stays one step ahead and
reads the loss of step i-1 while step i runs, as a loop that logs its
loss does; nothing else syncs.
"""

from __future__ import annotations

import math
import time

import numpy as np


def run(bench) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.lib.lm import build_model, token_sampler
    from benchmark.reference import gpt
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer, make_lm_batch

    traffic, config = bench.traffic, bench.config
    seed = bench.seed % (2 ** 31 - 1)
    L, mb = traffic["seq_len"], traffic["microbatch"]
    model = build_model(config, max_seq_len=L,
                        use_flash=traffic["use_flash"])
    trainer = LMTrainer(model, make_mesh(bench.devices))
    state = trainer.init_state(seed=seed)
    draw = token_sampler(model.vocab_size, traffic["zipf_offset"],
                         np.random.default_rng(seed))

    def batch():
        return trainer.put_batch(*make_lm_batch(draw((mb, L + 1))))

    jax.block_until_ready(state.params)
    bench.phase("init")

    # The plain float32 reference's loss on the first batch and the
    # initial weights, against the trainer's own first loss.
    x0, y0 = batch()
    rows = zip(np.asarray(x0), np.asarray(y0))
    want = float(np.mean([gpt.loss(state.params, jnp.asarray(x),
                                   jnp.asarray(y)) for x, y in rows]))
    bench.phase("reference")
    state, loss = trainer.train_step(state, x0, y0)
    got = float(np.mean(np.asarray(loss)))
    state, loss = trainer.train_step(state, *batch())
    jax.block_until_ready(loss)
    bench.phase("warm_up")

    losses, prev, steps = [], None, 0
    t_open = bench.open_window()
    while bench.elapsed() < bench.seconds:
        bench.tick()
        with bench.span("bench.put_batch"):
            x, y = batch()
        with bench.span("bench.train_step"):
            state, loss = trainer.train_step(state, x, y)
        if prev is not None:
            with bench.span("bench.read_loss"):
                losses.append(float(np.mean(np.asarray(prev))))
        prev = loss
        steps += 1
    with bench.span("bench.read_loss"):
        losses.append(float(np.mean(np.asarray(prev))))
    window_s = time.perf_counter() - t_open
    bench.close_window()

    k = max(1, len(losses) // 10)
    checks = {
        "losses_finite": all(map(math.isfinite, losses)),
        "loss_fell": float(np.mean(losses[-k:])) < float(np.mean(losses[:k])),
        "reference_agrees": abs(got - want) <= traffic["loss_tol"] * want,
    }
    bad = sum(not math.isfinite(v) for v in losses)
    tok_s = steps * mb * L / window_s
    return {
        "correct": all(checks.values()),
        "attempted": steps,
        "failed": bad,
        "values": {"train_throughput": tok_s},
        "counters": {"steps": steps, "train_s": window_s,
                     "items_per_s": tok_s, "seq_len": L},
        "notes": {"checks": checks, "loss_first": got, "loss_reference": want,
                  "loss_rel_diff": abs(got - want) / want,
                  "loss_start": float(np.mean(losses[:k])),
                  "loss_end": float(np.mean(losses[-k:]))},
    }
