"""Asynchronous step-dispatch pipeline — keep the device queue full.

JAX dispatch is asynchronous: a jitted train step returns device-array
futures immediately and the computation runs behind them. The naive loop
(reference part1/main.py:65-84 and our pre-round-6 engine) throws that
away by forcing every step's loss to host before dispatching the next —
``block_until_ready`` + ``float(loss)`` once per iteration drains the
device queue to empty, so dispatch, metrics, heartbeats and checkpoint
bookkeeping all sit on the critical path: every forced readback
serializes Python bookkeeping with device compute.

:class:`DispatchPipeline` is the engine-side fix: a bounded FIFO window
of in-flight result handles. The loop dispatches up to ``depth`` steps
back-to-back and only materializes a result when its handle is already
ready (``jax.Array.is_ready`` — a non-blocking poll) or the window is
full. When the window IS full, ONE ``jax.block_until_ready`` over the
whole window drains it — so the loop pays at most one forced
synchronization per ``depth`` steps (regression-tested by monkeypatching
``jax.block_until_ready`` in tests/test_dispatch_pipeline.py).

Delivery is strictly in submission order, so every consumer driven from
harvested results (``_LossWindow.account``, ``StepGuard.record``,
heartbeats, checkpoint cadence) observes the same sequence as the
synchronous loop — just up to ``depth`` steps later. ``depth=0``
degenerates to the synchronous semantics exactly: every submit delivers
before returning (the chaos drills and the reference's timing protocol
run this way; see docs/DESIGN.md §13 for the contract).
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable

import jax


def _handle_ready(value) -> bool:
    """Non-blocking readiness poll over a pytree of device arrays.

    Leaves without ``is_ready`` (host numpy, python scalars) count as
    ready. If ``jax.Array`` ever loses ``is_ready``, everything reports
    not-ready and the pipeline still works — it just always waits for a
    full window before the (single, batched) forced sync.
    """
    for leaf in jax.tree.leaves(value):
        fn = getattr(leaf, "is_ready", None)
        if fn is not None and not fn():
            return False
    return True


class DispatchPipeline:
    """Bounded in-order window of in-flight step results.

    ``submit(value, on_ready)`` enqueues one dispatched step's result
    handle together with the callback that materializes and accounts it.
    Callbacks fire in submission order:

    - opportunistically, whenever the oldest handle polls ready
      (zero forced syncs — the common case once compute is the
      bottleneck);
    - in a batch, when a submit would leave more than ``depth``
      undelivered handles: one ``jax.block_until_ready`` over the WHOLE
      window, then every callback — ≤1 forced sync per ``depth`` steps;
    - immediately, for ``submit(..., sync=True)`` (the timing window and
      chaos-exact-step iterations) and for :meth:`drain` at epoch end.

    Host-side stall accounting: ``host_gap_ms`` accumulates wall time
    spent inside forced ``block_until_ready`` calls — the part of the
    epoch where the host had nothing to do but wait on the device. The
    synchronous loop's gap is the whole per-step device latency; deeper
    windows shrink it toward zero (scripts/host_gap.py measures this).
    At depth > 0, ``submit(sync=True)`` drains are counted separately
    under ``sync_deliveries`` and accrue NO host_gap/forced_syncs: the
    caller used that path because it already blocked on the handle (the
    reference timing protocol), so charging the drain to the async
    window would overstate its cost. At depth 0 they stay in
    ``forced_syncs`` — the synchronous baseline's per-step sync is the
    very thing being measured.
    """

    def __init__(self, depth: int):
        if depth < 0:
            raise ValueError(f"dispatch depth must be >= 0, got {depth}")
        self.depth = depth
        self._queue: collections.deque = collections.deque()
        # Stats (reported via _LossWindow.epoch_stats / bench extra).
        self.forced_syncs = 0
        self.sync_deliveries = 0
        self.host_gap_ms = 0.0
        self.harvested = 0
        self.max_in_flight = 0

    def __len__(self) -> int:
        return len(self._queue)

    def submit(self, value: Any, on_ready: Callable[[Any], None],
               sync: bool = False) -> None:
        """Enqueue one result handle; may deliver any number of queued
        results (oldest first). ``sync=True`` delivers everything —
        including ``value`` — before returning."""
        self._queue.append((value, on_ready))
        if len(self._queue) > self.max_in_flight:
            self.max_in_flight = len(self._queue)
        if sync:
            # depth 0 IS the synchronous baseline: its per-submit drain
            # is exactly the forced sync deeper windows amortize away,
            # so it stays in forced_syncs/host_gap_ms. At depth > 0 a
            # sync submit only comes from the timing window, where the
            # caller already blocked on the handle — charged to
            # sync_deliveries so the async window's stats aren't
            # inflated by the timing protocol's mandatory syncs.
            self._force_drain(forced=self.depth == 0)
            return
        self._poll_ready()
        if len(self._queue) > self.depth:
            self._force_drain()

    def poll(self) -> None:
        """Deliver any already-finished prefix of the window (no sync)."""
        self._poll_ready()

    def drain(self) -> None:
        """Deliver everything still in flight (end of epoch)."""
        if self._queue:
            self._force_drain()

    def stats(self) -> dict:
        return {
            "dispatch_depth": self.depth,
            "forced_syncs": self.forced_syncs,
            "sync_deliveries": self.sync_deliveries,
            "host_gap_ms": round(self.host_gap_ms, 3),
            "harvested": self.harvested,
            "max_in_flight": self.max_in_flight,
        }

    # ---- internals -----------------------------------------------------

    def _poll_ready(self) -> None:
        while self._queue and _handle_ready(self._queue[0][0]):
            self._pop_deliver()

    def _force_drain(self, forced: bool = True) -> None:
        """``forced=False`` is the depth>0 ``submit(sync=True)`` path:
        the caller already blocked on the newest handle (and the FIFO
        backlog finished first on the same device stream), so the
        block below is ~free and is charged to ``sync_deliveries``
        instead of the async window's forced_syncs/host_gap_ms."""
        if forced:
            self.forced_syncs += 1
        else:
            self.sync_deliveries += 1
        t0 = time.perf_counter()
        # ONE blocking call for the whole window: the per-call overhead
        # is paid once, not per step. Delivery below then touches only
        # ready arrays.
        jax.block_until_ready([v for v, _ in self._queue])
        if forced:
            self.host_gap_ms += (time.perf_counter() - t0) * 1e3
        while self._queue:
            self._pop_deliver()

    def _pop_deliver(self) -> None:
        value, on_ready = self._queue.popleft()
        self.harvested += 1
        # A raising callback (TrainingDivergedError) propagates to the
        # epoch loop; later handles stay queued and are simply dropped
        # with the trainer — their steps never happened as far as the
        # harvested-results consumers are concerned.
        on_ready(value)


class StageScheduler:
    """Per-stage dispatch windows + tick accounting for the MPMD
    pipeline (round 10) — :class:`DispatchPipeline` generalized from
    one global window to one window per stage.

    The MPMD host loop (parallel/mpmd.py) calls :meth:`tick` once per
    (stage, tick) with that tick's validity bits; the scheduler
    classifies the tick into the 1F1B phases —

    - ``warmup``:   forward valid, backward not yet (the fill ramp);
    - ``steady``:   both valid (the 1F1B body, zero bubble);
    - ``cooldown``: backward only (the drain ramp);
    - ``idle``:     neither (this stage's share of the bubble) —

    and, when the caller hands it a device handle, bounds that stage's
    in-flight work through its own DispatchPipeline window (each stage
    dispatches independently, so one global window would let a fast
    early stage run arbitrarily far ahead of a slow late one).

    :meth:`step_done` is the per-step barrier: every stage's window
    drains (the guard must observe a completed step before the next
    dispatches) and the heartbeat hook fires — the same
    ``touch_heartbeat`` cadence the SPMD epoch loop keeps, so the
    watchdog and the chaos drills work unchanged on this rung.
    """

    PHASES = ("warmup", "steady", "cooldown", "idle")

    def __init__(self, pp_size: int, depth: int = 2,
                 heartbeat: Callable[[int], None] | None = None):
        if pp_size < 1:
            raise ValueError(f"pp_size must be >= 1, got {pp_size}")
        self.pp_size = pp_size
        self.windows = [DispatchPipeline(depth) for _ in range(pp_size)]
        self.heartbeat = heartbeat
        self.phase_counts = [dict.fromkeys(self.PHASES, 0)
                             for _ in range(pp_size)]
        self.ticks = [0] * pp_size
        self.steps = 0

    @staticmethod
    def classify(fwd: bool, bwd: bool) -> str:
        if fwd and bwd:
            return "steady"
        if fwd:
            return "warmup"
        if bwd:
            return "cooldown"
        return "idle"

    def tick(self, stage: int, fwd: bool, bwd: bool,
             handle=None) -> str:
        phase = self.classify(fwd, bwd)
        self.phase_counts[stage][phase] += 1
        self.ticks[stage] += 1
        if handle is not None:
            self.windows[stage].submit(handle, lambda _v: None)
        return phase

    def step_done(self, step: int) -> None:
        for w in self.windows:
            w.drain()
        self.steps += 1
        if self.heartbeat is not None:
            self.heartbeat(step)

    def bubble_fraction(self, stage: int) -> float:
        """This stage's idle share of its ticks so far — the measured
        per-stage bubble the bench compares to the analytic model."""
        t = self.ticks[stage]
        return self.phase_counts[stage]["idle"] / t if t else 0.0

    def stats(self) -> dict:
        return {
            "pp_size": self.pp_size,
            "steps": self.steps,
            "stages": [
                {"ticks": self.ticks[s],
                 **self.phase_counts[s],
                 "bubble_fraction": round(self.bubble_fraction(s), 4),
                 "window": self.windows[s].stats()}
                for s in range(self.pp_size)
            ],
        }


def depth_sweep(trainer, state, host_batches, depths,
                reps: int = 1, epoch: int = 0) -> tuple[dict, Any]:
    """Measure streaming-loop throughput and host-gap per dispatch depth.

    Runs ``Trainer.train_epoch`` over ``host_batches`` (a list of
    ``(images, labels)`` host tuples) once per depth in ``depths``
    (``reps`` times, keeping the best wall time — CI hosts are noisy),
    with the reference timing window disabled so every iteration past
    the first is eligible for async dispatch. The jitted step is shared
    across depths (depth is a host-loop property, not a compile-time
    one), so the sweep measures dispatch discipline, nothing else.

    Returns ``(results, state)`` where ``results[str(depth)]`` holds
    ``steps_per_sec`` / ``host_gap_ms`` / ``forced_syncs`` / ``wall_s``.
    Shared by scripts/host_gap.py and bench.py so the committed artifact
    and the benchmark record the same protocol.
    """
    cfg = trainer.config
    saved = (cfg.dispatch_depth, cfg.timing_first_iter,
             cfg.timing_last_iter)
    results: dict = {}
    try:
        # Only iteration 0 stays synchronous (warm-up barrier, the
        # reference's discarded iteration 0).
        cfg.timing_first_iter, cfg.timing_last_iter = 1, 0
        for d in depths:
            cfg.dispatch_depth = int(d)
            best = None
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                state, stats = trainer.train_epoch(
                    state, list(host_batches), epoch=epoch,
                    log=lambda s: None)
                wall = time.perf_counter() - t0
                cell = {
                    "steps_per_sec": round(stats["iters"] / wall, 3),
                    "host_gap_ms": stats.get("host_gap_ms", 0.0),
                    "forced_syncs": stats.get("forced_syncs", 0),
                    "wall_s": round(wall, 4),
                }
                if best is None or cell["steps_per_sec"] > \
                        best["steps_per_sec"]:
                    best = cell
            results[str(int(d))] = best
    finally:
        (cfg.dispatch_depth, cfg.timing_first_iter,
         cfg.timing_last_iter) = saved
    return results, state
