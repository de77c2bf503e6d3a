"""Fleet resilience: the serving mirror of the round-5 training fault
model.

The training half earned its failure handling over three rounds
(StepGuard, verified checkpoints, elastic live-resharding); this module
gives the serving tier the same discipline. Three pieces:

- :class:`ReplicaHealth` — per-replica health state the Router keeps.
  A replica that raises out of ``step()`` (or overruns the optional
  step deadline) goes ``unhealthy``; re-admission is by probe with
  exponential backoff (``backoff * 2**(failures-1)``, capped), so a
  flapping replica gets exponentially rarer chances while a recovered
  one rejoins after a single successful probe. The clock is
  injectable so the backoff schedule is unit-testable without sleeps.

- :class:`ServeFaultInjector` — the serve-side chaos hooks, riding the
  training :class:`~tpu_ddp.resilience.chaos.FaultInjector` spec
  grammar, seed, and sentinel machinery unchanged
  (``TPU_DDP_CHAOS_FAULTS``; kinds in
  ``tpu_ddp.resilience.chaos.SERVE_FAULT_KINDS``). ``rank`` in a spec
  is the REPLICA index — the Router stamps each replica's injector
  with its position — and ``step`` is that replica's engine-step
  counter (``edge-drop`` counts edge deliveries instead). Every kind
  is one-shot by step match, so a crashed-then-probed replica does not
  re-crash and re-admission is actually reachable.

- :func:`continuation_of` — the deterministic-migration primitive.
  Because sampling is stateless keyed on ``fold_in(seed, position)``
  (serve/engine.py, round 12), a request replayed elsewhere from
  ``prompt + tokens_so_far`` samples its next token at exactly the
  position key the undisturbed run would have used: the continuation
  prompt has length ``P + g``, so its first sampled token is keyed at
  position ``P + g`` — the original's token ``g``. Migration is
  therefore BITWISE invisible in the token stream, which is the
  testable contract (tests/test_fleet_resilience.py).

What is lost on a replica crash: the replica's KV pages and any decode
step in flight. What is replayed: every undone request, from its
prompt plus tokens already streamed (prefill is recomputed — KV pages
are not migrated between replica pools). What is never lost: tokens
already handed to the caller, and the accounting identity
``completed + cancelled + shed == submitted``.
"""

from __future__ import annotations

import os
import time

import numpy as np

from tpu_ddp.resilience.chaos import FaultInjector

HEALTHY = "healthy"
UNHEALTHY = "unhealthy"


class ReplicaCrashError(RuntimeError):
    """Raised by chaos (or a genuinely broken replica) out of
    ``step()`` — the signal the Router converts into unhealthy state
    plus request migration."""


class ReplicaHealth:
    """Health state machine for one replica: healthy <-> unhealthy
    with exponential-backoff probing."""

    def __init__(self, backoff_s: float = 0.2, backoff_cap_s: float = 30.0,
                 clock=time.monotonic):
        if backoff_s <= 0:
            raise ValueError(f"backoff_s must be > 0, got {backoff_s}")
        self.backoff_s = float(backoff_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.clock = clock
        self.state = HEALTHY
        self.failures = 0          # consecutive, reset on recovery
        self.next_probe_at = 0.0

    @property
    def healthy(self) -> bool:
        return self.state == HEALTHY

    def mark_failure(self) -> float:
        """Record one failure; returns the backoff until the next
        probe (doubling per consecutive failure, capped)."""
        self.failures += 1
        self.state = UNHEALTHY
        wait = min(self.backoff_s * 2 ** (self.failures - 1),
                   self.backoff_cap_s)
        self.next_probe_at = self.clock() + wait
        return wait

    def mark_recovered(self) -> None:
        self.state = HEALTHY
        self.failures = 0
        self.next_probe_at = 0.0

    def probe_due(self) -> bool:
        """True when an unhealthy replica has served its backoff and
        may be probed for re-admission."""
        return self.state == UNHEALTHY \
            and self.clock() >= self.next_probe_at


def continuation_of(request):
    """The (prompt, max_new_tokens) a migrated replay submits: the
    original prompt extended by every token already streamed, with the
    generation budget shrunk by the same amount. Stateless sampling
    keyed on (seed, position) makes the replayed stream bitwise equal
    to the undisturbed one."""
    if request.tokens:
        prompt = np.concatenate(
            [np.asarray(request.prompt, np.int32),
             np.asarray(request.tokens, np.int32)])
    else:
        prompt = np.asarray(request.prompt, np.int32)
    return prompt, request.max_new_tokens - len(request.tokens)


class ServeFaultInjector(FaultInjector):
    """Serve-side fault hooks over the shared chaos spec machinery.

    Engines construct one (when ``TPU_DDP_CHAOS_FAULTS`` is set) and
    call :meth:`replica_step` at the top of every ``step()``;
    DisaggEngine additionally consults :meth:`edge_drop_fires` per
    edge delivery, and both decode paths consult :meth:`poison_fires`
    before building a decode bank. Training kinds in the same env are
    ignored here (and vice versa), so one spec string can drill a
    whole train+serve stack.
    """

    @classmethod
    def from_env(cls, rank: int | None = 0) -> "ServeFaultInjector":
        inj = super().from_env(rank=rank)
        # Serve processes are single-host: default the rank (replica
        # index) to 0 instead of jax.process_index(); the Router
        # overwrites it with the replica's actual position.
        if inj._rank is None:
            inj._rank = 0
        return inj

    def set_rank(self, rank: int) -> None:
        """The Router stamps each replica's injector with its index so
        ``:rank=R`` specs target one replica of a fleet."""
        self._rank = int(rank)

    def replica_step(self, step: int) -> None:
        """Top-of-``step()`` faults: ``slow-replica`` sleeps once
        (``TPU_DDP_CHAOS_SLOW_S``) so a deadline-armed router sees the
        overrun; ``replica-crash`` raises. Both are one-shot (exact
        step match + sentinel), so the post-backoff probe of the same
        replica succeeds and re-admission is reachable."""
        for spec in self.specs:
            if spec.kind == "slow-replica" and self._fires(spec, step):
                self._announce(spec, step)
                self._mark_sentinel(spec, step)
                time.sleep(self.slow_s)
        for spec in self.specs:
            if spec.kind == "replica-crash" and self._fires(spec, step):
                self._announce(spec, step)
                self._mark_sentinel(spec, step)
                raise ReplicaCrashError(
                    f"chaos: replica {spec.rank} crashed at engine "
                    f"step {step}")

    def edge_drop_fires(self, delivery: int) -> bool:
        """True when the ``delivery``-th KV-edge transfer must be
        lost in flight (the decode worker then falls back to local
        chunked prefill)."""
        for spec in self.specs:
            if spec.kind == "edge-drop" and self._fires(spec, delivery):
                self._announce(spec, delivery)
                self._mark_sentinel(spec, delivery)
                return True
        return False

    def publisher_death_fires(self, push_n: int) -> bool:
        """True when the ``push_n``-th weight publish must find the
        publisher dead (subscribers then keep serving last-good and
        count the loss; nothing crashes)."""
        for spec in self.specs:
            if spec.kind == "publisher-death" \
                    and self._fires(spec, push_n):
                self._announce(spec, push_n)
                self._mark_sentinel(spec, push_n)
                return True
        return False

    def push_stall_fires(self, push_n: int) -> bool:
        """True when the ``push_n``-th weight push must stall in
        flight (delivery delayed until the trainer's staleness gate
        flushes it — a delay drill, not a loss drill)."""
        for spec in self.specs:
            if spec.kind == "push-stall" \
                    and self._fires(spec, push_n):
                self._announce(spec, push_n)
                self._mark_sentinel(spec, push_n)
                return True
        return False

    def poison_due(self, step: int) -> bool:
        """Whether :meth:`poison_fires` would fire at this engine step,
        asked without announcing or marking it: ``ServeEngine`` runs one
        decode step ahead of its readback and comes to rest before a
        step that may corrupt pages from the host."""
        return any(spec.kind == "nonfinite-logits"
                   and self._fires(spec, step) for spec in self.specs)

    def poison_fires(self, step: int) -> bool:
        """True when this engine step must corrupt one live request's
        KV pages with NaN (the ``nonfinite-logits`` drill: the decode
        bank's in-graph finiteness check must quarantine exactly the
        poisoned request)."""
        for spec in self.specs:
            if spec.kind == "nonfinite-logits" \
                    and self._fires(spec, step):
                self._announce(spec, step)
                self._mark_sentinel(spec, step)
                return True
        return False

    # ---- load-surge kinds (consumed by the DRIVE loop, not a replica;
    # chaos decides WHEN the surge lands, the drill decides what burst
    # to submit — see scripts/serve_chaos_sweep.py) -----------------------

    def flash_crowd_fires(self, step: int) -> bool:
        """True when a fleet-wide load surge must land at this drive
        step (the autoscaler's hysteresis/cooldown drill)."""
        for spec in self.specs:
            if spec.kind == "flash-crowd" and self._fires(spec, step):
                self._announce(spec, step)
                self._mark_sentinel(spec, step)
                return True
        return False

    def tenant_storm_fires(self, step: int) -> str | None:
        """The storming tenant's name when a single-tenant flood must
        land at this drive step (the WFQ-isolation drill), else
        None."""
        for spec in self.specs:
            if spec.kind == "tenant-storm" and self._fires(spec, step):
                self._announce(spec, step)
                self._mark_sentinel(spec, step)
                return spec.tenant
        return None


def serve_chaos_active() -> bool:
    """True when the chaos env is set at all — engines then construct
    a :class:`ServeFaultInjector` (specs with only training kinds are
    harmless: no serve hook matches them)."""
    from tpu_ddp.resilience.chaos import CHAOS_ENV
    return bool(os.environ.get(CHAOS_ENV))


__all__ = [
    "HEALTHY",
    "UNHEALTHY",
    "ReplicaCrashError",
    "ReplicaHealth",
    "ServeFaultInjector",
    "continuation_of",
    "serve_chaos_active",
]
