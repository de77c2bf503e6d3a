"""The closed online-RL scenario: generate → score → train → publish.

The loop the whole subsystem exists for (ROADMAP: online post-training
colocates a trainer and a generation fleet): a ``ServeEngine`` (or
fleet) generates rollouts from the CURRENT served version, a scorer
ranks them, the trainer consumes the best ones as a training batch
(rejection-sampling fine-tuning — the simplest honest member of the
online-RL family: no advantage estimator, just best-of-n selection +
LM loss on the winners), and the publisher streams the updated weights
back into the engine live. No restart, no drain: generation for round
``r+1`` runs on the weights round ``r`` trained, while any still-open
requests finish their current token on the old version.

Geometry contract: all prompts share one length and every rollout runs
to exactly ``max_new_tokens`` (no EOS), so the selected rollouts stack
into uniform ``(B, P + max_new)`` rows for ``make_lm_batch`` — no
padding, no loss masking. Sampling temperature must be > 0 (best-of-n
over identical greedy rollouts selects nothing).

``scripts/publish_sweep.py`` benchmarks this loop; the scenario test
(tests/test_publish.py) pins that the engine provably serves
trainer-updated weights — digests equal on both ends, versions
advanced, generations changed.

Speculation composes (DESIGN.md §26): a speculative engine
(``spec_k > 0``) multiplies rollout generation throughput, and with
an int8 draft or target the engine's ``swap_params`` re-derives the
quantized tree on every publisher flip — the draft-distill-and-push
loop: each round's draft is re-quantized FROM the weights that round
trained, so the draft never serves a stale version (the per-round
report pins ``speculative.draft_version == engine_version``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from tpu_ddp.train.lm import make_lm_batch


@dataclasses.dataclass
class Rollout:
    """One scored generation."""

    prompt: np.ndarray
    tokens: list
    logprobs: list
    reward: float = 0.0
    versions: tuple = ()      # param versions the tokens sampled under

    def row(self) -> np.ndarray:
        """prompt + generation as one packed LM training row."""
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.tokens, np.int32)])


def make_prompts(n: int, vocab_size: int, prompt_len: int,
                 seed: int = 0) -> list:
    """Deterministic fixed-length prompts (the loadgen analogue for
    the rollout loop)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab_size, size=prompt_len).astype(np.int32)
            for _ in range(n)]


def mean_logprob_scorer(rollout: Rollout) -> float:
    """Default scorer: mean sampled logprob — deterministic, needs no
    external reward model, and selecting on it (best-of-n) pushes the
    policy toward its own high-likelihood continuations (the
    self-distillation degenerate case of RFT; swap in a real reward
    model via the ``scorer`` argument)."""
    return float(np.mean(rollout.logprobs)) if rollout.logprobs else 0.0


def generate_rollouts(engine, prompts, *, max_new_tokens: int,
                      temperature: float, round_idx: int,
                      samples_per_prompt: int = 2,
                      scorer=mean_logprob_scorer) -> list:
    """Submit ``samples_per_prompt`` stochastic samples per prompt,
    drain the engine, score. Seeds fold (round, prompt, sample) so
    every rollout is distinct and the whole loop is replayable."""
    if temperature <= 0:
        raise ValueError("online rollouts need temperature > 0 "
                         "(best-of-n over greedy duplicates is vacuous)")
    handles = []
    for i, p in enumerate(prompts):
        for k in range(samples_per_prompt):
            seed = 100003 * round_idx + 1009 * i + k
            handles.append((i, engine.submit(
                p, max_new_tokens, temperature=temperature, seed=seed)))
    engine.run()
    rollouts = []
    for i, req in enumerate(handles):
        pi, r = req
        if not r.done or r.cancelled or r.shed or r.quarantined:
            continue
        ro = Rollout(prompt=prompts[pi], tokens=list(r.tokens),
                     logprobs=list(r.logprobs),
                     versions=tuple(sorted(set(r.token_versions))))
        ro.reward = scorer(ro)
        rollouts.append((pi, ro))
    return rollouts


def select_best(rollouts, n_prompts: int) -> list:
    """Best-of-n per prompt: the highest-reward rollout of each
    prompt, in prompt order — the training batch."""
    best: dict = {}
    for pi, ro in rollouts:
        if pi not in best or ro.reward > best[pi].reward:
            best[pi] = ro
    return [best[pi] for pi in range(n_prompts) if pi in best]


def run_online_loop(trainer, engine, publisher, state, *, rounds: int,
                    prompts, max_new_tokens: int,
                    temperature: float = 0.7,
                    samples_per_prompt: int = 2,
                    scorer=mean_logprob_scorer,
                    settle_steps: int = 8):
    """The closed loop. Returns ``(state, report)`` where ``report``
    carries per-round loss/reward/version plus the publisher's final
    stats. ``settle_steps`` idle engine steps after the last round
    land any still-staged buckets, so the caller observes the final
    version served (each engine step stages at most one bucket)."""
    report = {"rounds": []}
    for r in range(rounds):
        rollouts = generate_rollouts(
            engine, prompts, max_new_tokens=max_new_tokens,
            temperature=temperature, round_idx=r,
            samples_per_prompt=samples_per_prompt, scorer=scorer)
        batch = select_best(rollouts, len(prompts))
        if not batch:
            raise RuntimeError(f"round {r}: no rollout survived "
                               "(all shed/cancelled/quarantined?)")
        rows = np.stack([ro.row() for ro in batch])
        inputs, targets = make_lm_batch(rows)
        x, y = trainer.put_batch(inputs, targets)
        state, loss = trainer.train_step(state, x, y)
        publisher.after_step(state, int(state.step))
        rep = {
            "round": r, "loss": float(np.mean(np.asarray(loss))),
            "reward_mean": float(np.mean([ro.reward for ro in batch])),
            "published_version": publisher.version,
            "engine_version": getattr(engine, "param_version", 0),
        }
        if getattr(engine, "spec_k", 0) > 0 \
                and hasattr(engine, "spec_stats"):
            # Draft provenance: swap_params re-derived the draft from
            # the engine's current weights, so the draft's version IS
            # the engine's — pinned per round by the scenario test.
            rep["speculative"] = dict(
                engine.spec_stats(),
                draft_version=getattr(engine, "param_version", 0))
        report["rounds"].append(rep)
    for _ in range(settle_steps):
        engine.step()
    report["publisher"] = publisher.stats()
    report["subscribers"] = [s.stats() for s in publisher.subscribers]
    if getattr(engine, "spec_k", 0) > 0 and hasattr(engine, "spec_stats"):
        report["speculative"] = engine.spec_stats()
    return state, report


__all__ = [
    "Rollout",
    "generate_rollouts",
    "make_prompts",
    "mean_logprob_scorer",
    "run_online_loop",
    "select_best",
]
