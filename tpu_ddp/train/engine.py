"""Train/eval engine — the reference's L2 (``train_model``/``test_model``),
rebuilt as jit-compiled XLA programs.

The reference loop body (identical skeleton in all four parts,
part2/part2b/main.py:124-132) is::

    optimizer.zero_grad(); out = model(x); loss = CE(out, y)
    loss.backward(); [sync_gradients(...)]; optimizer.step()

Here the entire body — forward, backward, gradient sync (one of the four
strategies), optimizer update — is ONE jitted function. On a device mesh the
step is ``shard_map``'d: batch sharded over the ``dp`` axis, params and
optimizer state replicated, the sync strategy's XLA collectives riding ICI.
Instrumentation parity: running-loss print every 20 iterations and the
iteration-1..39 ns timer (reference part1/main.py:82-91) both survive, with
``block_until_ready`` before the clock stops.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_ddp.data.prefetch import prefetch_to_device
from tpu_ddp.train.pipeline import DispatchPipeline
from tpu_ddp.ops.loss import cross_entropy_loss, softmax_cross_entropy
from tpu_ddp.ops.metrics import top1_correct
from tpu_ddp.ops.optim import SGD
from tpu_ddp.parallel.mesh import DATA_AXIS
from tpu_ddp.parallel.sync import canonical_strategy, get_sync_strategy
from tpu_ddp.resilience.guard import (StepGuard, nonfinite_flag,
                                      select_update)
from tpu_ddp.utils.config import TrainConfig
from tpu_ddp.utils.metrics import MetricsLogger
from tpu_ddp.utils.profiling import (DDP_EVAL_STEP, DDP_TRAIN_MULTI_STEP,
                                     DDP_TRAIN_STEP, program, span,
                                     spanned)
from tpu_ddp.utils.timing import IterationTimer


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0
    # Gradient-compression carry (parallel/compress.py): None unless the
    # compressor is stateful (int8's stochastic-rounding seed counter +
    # error-feedback residual). Threaded through the jitted step, donated
    # with params/opt_state, checkpointed, reset on restore-mismatch.
    comp_state: Any = None


class _LossWindow:
    """Running-loss window with the reference's print/metric cadence
    (loss every ``log_every`` iters, part1/main.py:82-84; timing report
    at the window's last iteration) — ONE implementation shared by the
    per-step and K-per-dispatch epoch loops so their output cannot
    drift (tests assert the two loops print identical lines)."""

    def __init__(self, cfg, metrics, timer, epoch: int, log):
        self._cfg = cfg
        self._metrics = metrics
        self._timer = timer
        self._epoch = epoch
        self._log = log
        self._running = 0.0
        self._window = 0
        self.last_loss = 0.0
        self.iters = 0

    def account(self, it: int, local_loss: float, step: int) -> None:
        cfg = self._cfg
        self._running += local_loss
        self._window += 1
        self.last_loss = local_loss
        self.iters += 1
        if it % cfg.log_every == cfg.log_every - 1:
            # Divide by the iterations actually in the window — after a
            # mid-epoch resume the first window is shorter.
            window_loss = self._running / max(self._window, 1)
            self._log(f"[epoch {self._epoch}, iter {it + 1}] "
                      f"loss: {window_loss:.3f}")
            self._metrics.log("train_iter", epoch=self._epoch,
                              iter=it + 1, step=step,
                              loss=round(window_loss, 5))
            self._running = 0.0
            self._window = 0
        if it == cfg.timing_last_iter:
            self._log(self._timer.report(prefix=f"[epoch {self._epoch}] "))

    def epoch_stats(self, pipeline: dict | None = None) -> dict:
        timer = self._timer
        # timed_iters makes a steps_per_dispatch K that swallows most of
        # the timing window VISIBLE in the metrics stream (a K-group
        # that starts before timer.first_iter is deliberately untimed —
        # keeping compile out of the window — so the average may rest on
        # few samples; round-2 advisor finding). ``pipeline`` carries
        # the dispatch window's stall accounting (train/pipeline.py)
        # into the same epoch record.
        pipeline = pipeline or {}
        if "host_gap_ms" in pipeline:
            self._metrics.observe("host_gap_ms",
                                  pipeline["host_gap_ms"])
        self._metrics.log("epoch", epoch=self._epoch, iters=self.iters,
                          avg_iter_s=timer.average_s,
                          timed_iters=timer.count,
                          last_loss=round(self.last_loss, 5),
                          **pipeline)
        return {
            "avg_iter_ns": timer.average_ns,
            "avg_iter_s": timer.average_s,
            "timed_iters": timer.count,
            "last_loss": self.last_loss,
            "iters": self.iters,
            **pipeline,
        }


class Trainer:
    """Wires model + optimizer + sync strategy into jitted train/eval steps.

    ``mesh=None`` is the part1 configuration (single device, plain ``jit``);
    with a mesh, the step is ``shard_map``'d over it and ``strategy`` picks
    which of the four ladder rungs synchronizes the gradients.
    """

    def __init__(
        self,
        model,
        config: TrainConfig | None = None,
        strategy: str = "none",
        mesh: Mesh | None = None,
        metrics: "MetricsLogger | None" = None,
        clip_grad_norm: float | None = None,
    ):
        self.model = model
        self.config = config or TrainConfig()
        # Autotuning fallback hook (tpu_ddp/tune/): parts/common.py
        # resolves BEFORE get_model so model-level knobs apply; direct
        # Trainer construction resolves here with model_built=True
        # (model-level overrides are dropped with a warning). resolve()
        # returns a config with autotune="off", so this cannot recurse
        # through the trial runner's own Trainer constructions.
        if getattr(self.config, "autotune", "off") != "off":
            from tpu_ddp import tune
            self.config = tune.resolve(self.config, strategy=strategy,
                                       mesh=mesh, model_built=True)
        # Memory policy (tpu_ddp/memory/): imprint the config's remat /
        # act_dtype onto the model. Models carry the policy as STATIC
        # dataclass fields and apply it inside their own ``apply``, so
        # every jit surface below — plain jit, shard_map, the K-step
        # scan, FSDP, the comp_state carry — traces the policied
        # program with no per-surface wiring. Runs AFTER the autotune
        # resolve so tuned remat values reach the model.
        from tpu_ddp.memory import apply_policy
        self.model = apply_policy(
            self.model,
            remat=getattr(self.config, "remat", "none"),
            act_dtype=getattr(self.config, "act_dtype", "compute"))
        # Global-norm gradient clipping (round-3 verdict item 6):
        # torch.nn.utils.clip_grad_norm_ semantics. Applied to the
        # SYNCED gradients, so every rung clips by the same global norm:
        # replicated strategies compute it locally (grads identical
        # everywhere after sync), ZeRO-1 from its dp-scattered slices
        # (ZeRO1.apply_scattered), FSDP from its flat dp shards — all
        # exactly equal up to reduction order (tests/test_clip_norm.py).
        # Exception: strategy 'none' never syncs, so each replica clips
        # by its OWN local norm and the clipped rung diverges across
        # replicas by design (consistent with that rung's no-sync
        # semantics) — warned below so nobody assumes torch-style
        # global clipping there.
        if clip_grad_norm is not None and clip_grad_norm <= 0:
            raise ValueError(
                f"clip_grad_norm must be > 0, got {clip_grad_norm}")
        if (clip_grad_norm is not None and mesh is not None
                and mesh.shape[DATA_AXIS] > 1
                and canonical_strategy(strategy) == "none"):
            import warnings
            warnings.warn(
                "clip_grad_norm with strategy 'none': each replica clips "
                "by its own LOCAL gradient norm (no sync), so replicas "
                "diverge; use a syncing rung for global-norm clipping.",
                stacklevel=2)
        self.clip_grad_norm = clip_grad_norm
        self.metrics = metrics if metrics is not None else MetricsLogger()
        self.strategy_name = strategy
        self.sync_fn = get_sync_strategy(strategy)
        self.mesh = mesh
        self.is_zero = canonical_strategy(strategy) == "zero"
        self.optimizer = SGD(
            learning_rate=self.config.learning_rate,
            momentum=self.config.momentum,
            weight_decay=self.config.weight_decay,
            use_pallas=self.config.pallas_sgd,
        )
        self.is_fsdp = canonical_strategy(strategy) == "fsdp"
        self._dp = mesh.shape[DATA_AXIS] if mesh is not None else 1
        # Step guard (resilience/guard.py). The jit-side skip flag is
        # agreed across replicas with one scalar psum — EXCEPT under
        # strategy 'none', whose contract is zero cross-replica
        # communication (each replica guards its own local step, the
        # same per-replica semantics that rung has for clipping).
        self._guard_axis = (
            DATA_AXIS if mesh is not None
            and canonical_strategy(strategy) != "none" else None)
        self.guard = (StepGuard(self.config.guard_max_bad_steps,
                                metrics=self.metrics)
                      if self.config.guard_nonfinite else None)
        if self.is_zero:
            if mesh is None:
                raise ValueError("strategy 'zero' shards optimizer state "
                                 "over the dp axis and requires a mesh")
            from tpu_ddp.parallel.zero import ZeRO1
            self.optimizer = ZeRO1(self.optimizer, DATA_AXIS, self._dp,
                                   template=self._params_template())
        if self.is_fsdp:
            if mesh is None:
                raise ValueError("strategy 'fsdp' shards parameters over "
                                 "the dp axis and requires a mesh")
            from tpu_ddp.parallel.zero import ZeRO3
            self.zero3 = ZeRO3(self.optimizer, DATA_AXIS, self._dp,
                               template=self._params_template())
        # Gradient wire compression (parallel/compress.py). Wraps any
        # SYNCING rung; under 'none' (no sync) or without a dp>1 mesh
        # there is no collective to compress, so the spec degrades to the
        # no-op with a warning rather than silently changing semantics.
        from tpu_ddp.parallel.compress import (REPLICATED_KINDS,
                                               get_compressor)
        self.compressor = get_compressor(self.config.grad_compress)
        canon = canonical_strategy(strategy)
        self._comp_active = (self.compressor.spec != "none"
                             and mesh is not None and self._dp > 1
                             and canon != "none")
        if self.compressor.spec != "none" and not self._comp_active:
            import warnings
            warnings.warn(
                f"grad_compress={self.compressor.spec!r} needs a dp>1 "
                "mesh and a syncing strategy (got "
                f"strategy={strategy!r}, dp={self._dp}); compression "
                "disabled.", stacklevel=2)
            self.compressor = get_compressor("none")
        self._comp_stateful = (self._comp_active
                               and self.compressor.stateful)
        self._comp_kind = canon if canon in REPLICATED_KINDS else None
        if self._comp_stateful:
            self._comp_template = self.compressor.init_state(
                self._params_template(), self._dp, abstract=True)
            self._comp_specs = self.compressor.state_specs(
                self._comp_template)
        else:
            self._comp_template = None
            self._comp_specs = None
        # Overlapped bucketized collectives (parallel/overlap.py):
        # torch DDP's reducer — per-bucket collectives issued from
        # inside the backward — plus the 2004.13336 sharded weight
        # update on the all_reduce/fused rungs. Needs a dp>1 mesh and a
        # replicated syncing rung (ZeRO/FSDP already interleave their
        # collectives naturally; 'none' has nothing to overlap), so the
        # knob degrades with a warning otherwise — the compression
        # contract above.
        self._overlap_active = (
            getattr(self.config, "overlap", False) and mesh is not None
            and self._dp > 1 and canon in REPLICATED_KINDS)
        if getattr(self.config, "overlap", False) \
                and not self._overlap_active:
            import warnings
            warnings.warn(
                "overlap=True needs a dp>1 mesh and a replicated "
                f"syncing rung (got strategy={strategy!r}, "
                f"dp={self._dp}); bucketed overlap disabled.",
                stacklevel=2)
        self._overlap = None
        self._sharded_update = None
        self._publisher = None
        if self._overlap_active:
            self._build_overlap()
        if mesh is not None:
            self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
            self._repl_sharding = NamedSharding(mesh, P())
            self._param_put_sharding = (
                NamedSharding(mesh, P(DATA_AXIS)) if self.is_fsdp
                else self._repl_sharding)
        self._train_step = self._build_train_step()
        self._eval_step = self._build_eval_step()
        # TPU_DDP_AUDIT=warn|error: static donation/precision audit of
        # the train step before it burns a single real step
        # (tpu_ddp/analysis/gate.py). The audit's compile lands in the
        # jit cache, so it is the first step's compile, not an extra.
        if getattr(self.config, "audit", "off") != "off":
            from tpu_ddp.analysis.gate import maybe_audit_trainer
            maybe_audit_trainer(self)

    # ---- state ---------------------------------------------------------

    def _params_template(self):
        """Abstract canonical-shape params tree (no compute)."""
        return jax.eval_shape(lambda: self.model.init(jax.random.key(0)))

    def _build_overlap(self):
        """(Re)build the bucket plan + overlap/sharded-update wrappers
        against the current mesh size (construction and rebind_mesh)."""
        from tpu_ddp.parallel.overlap import (SCATTER_KINDS, BucketPlan,
                                              OverlapSync, ShardedUpdate)
        canon = canonical_strategy(self.strategy_name)
        plan = BucketPlan(self._params_template(),
                          self.config.bucket_mb)
        self._overlap = OverlapSync(
            plan, canon, DATA_AXIS, self._dp,
            compressor=self.compressor if self._comp_active else None)
        # all_reduce/fused produce a scattered reduction, so the
        # optimizer runs on 1/N payload shards; gather_scatter keeps
        # its root-mean semantics and a replicated update.
        self._sharded_update = (
            ShardedUpdate(self.optimizer, plan, DATA_AXIS, self._dp)
            if canon in SCATTER_KINDS else None)

    def _opt_spec(self):
        """shard_map prefix spec for the optimizer state: replicated for
        the replicated strategies, dp-sharded flat leaves under ZeRO,
        FSDP and the overlapped sharded update."""
        if self.is_fsdp:
            return self.zero3.state_specs()
        if self._sharded_update is not None:
            return self._sharded_update.state_specs()
        return self.optimizer.state_specs(P())

    def _param_spec(self):
        """shard_map prefix spec for the parameters: flat dp shards
        under FSDP, replicated otherwise."""
        return P(DATA_AXIS) if self.is_fsdp else P()

    def _opt_shardings(self, opt_state):
        """Broadcast the prefix spec over the concrete state tree."""
        return jax.tree.map(
            lambda spec, sub: jax.tree.map(
                lambda _: NamedSharding(self.mesh, spec), sub),
            self._opt_spec(), opt_state,
            is_leaf=lambda x: isinstance(x, P))

    def init_state(self, seed: int | None = None) -> TrainState:
        """Parameter init from the shared seed — correctness invariant (i)
        of the reference (seed 89395 on every node, part1/main.py:115-117):
        every replica deterministically builds identical parameters.
        Under FSDP the full tree is flattened and each worker keeps its
        1/N shard of every leaf."""
        seed = self.config.seed if seed is None else seed
        params = self.model.init(jax.random.key(seed))
        if self.is_fsdp:
            params = self.zero3.shard_params(params)
            opt_state = self.zero3.init(params)
        elif self._sharded_update is not None:
            opt_state = self._sharded_update.init(params)
        else:
            opt_state = self.optimizer.init(params)
        if self.mesh is not None:
            params = jax.device_put(params, self._param_put_sharding)
            opt_state = jax.device_put(opt_state,
                                       self._opt_shardings(opt_state))
        comp_state = None
        if self._comp_stateful:
            comp_state = self.compressor.init_state(
                self._params_template(), self._dp, seed=seed)
            comp_state = jax.device_put(comp_state,
                                        self._comp_shardings())
        return TrainState(params=params, opt_state=opt_state,
                          comp_state=comp_state)

    def _comp_shardings(self):
        """NamedShardings for the compressor carry: seed replicated,
        residual leaves dp-sharded on their leading axis."""
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self._comp_specs,
                            is_leaf=lambda x: isinstance(x, P))

    # ---- checkpoint / resume (no reference equivalent, SURVEY.md §5) ---

    def sharding_plan(self):
        """This trainer's layout contract as a serializable
        :class:`~tpu_ddp.parallel.redistribute.ShardingPlan` — the same
        spec trees the shard_map surfaces close over, lifted out so a
        checkpoint, a membership epoch, or a test can re-resolve them
        against a different mesh."""
        from tpu_ddp.parallel.redistribute import ShardingPlan
        if self.mesh is not None:
            mesh_axes = tuple((str(n), int(s))
                              for n, s in self.mesh.shape.items())
        else:
            mesh_axes = ((DATA_AXIS, 1),)
        return ShardingPlan(
            strategy=self.strategy_name,
            mesh_axes=mesh_axes,
            param_specs=self._param_spec(),
            opt_specs=self._opt_spec(),
            comp_specs=self._comp_specs,
            batch_spec=P(DATA_AXIS),
        )

    def state_to_host(self, state: TrainState,
                      local_only: bool = False) -> dict:
        """Pull ``state`` to CANONICAL host numpy form on every process.

        The gather runs LEAF BY LEAF (the bounded decomposition of
        arxiv 2112.01075): the device-memory peak is one replicated
        leaf, never the whole tree. Both checkpointing and live
        resharding feed off this one path, so a canonical host tree is
        *the* portable representation of training state.

        ``local_only=True`` is the membership-change path: a peer may
        already be dead, so no cross-process collective may run. State
        sharded across processes (ZeRO/FSDP at process_count > 1)
        cannot be pulled locally — that raises, and the elastic loop
        falls back to restart-from-checkpoint. The dp-sharded
        compression residual is likewise skipped (reset after the
        reshard; it is an accelerator, not model state)."""
        multiproc = jax.process_count() > 1
        params = state.params
        opt_state = state.opt_state
        comp_state = state.comp_state
        if local_only and multiproc and (self.is_zero or self.is_fsdp
                                         or self._sharded_update
                                         is not None):
            raise RuntimeError(
                "live state of a cross-process ZeRO/FSDP/sharded-update "
                "run cannot be snapshotted without the lost peer's "
                "shards; this membership change needs a checkpoint "
                "restart")
        if comp_state is not None and self.mesh is not None:
            if local_only and multiproc:
                comp_state = None
            else:
                # The error-feedback residual is dp-sharded (each
                # device's own quantization error); gather it whole.
                from tpu_ddp.utils.checkpoint import gather_tree_to_host
                comp_state = gather_tree_to_host(comp_state,
                                                 self._repl_sharding)
        if self.mesh is not None and (self.is_zero or self.is_fsdp
                                      or self._sharded_update
                                      is not None):
            from tpu_ddp.utils.checkpoint import gather_tree_to_host
            opt_state = gather_tree_to_host(opt_state,
                                            self._repl_sharding)
            if self.is_fsdp:
                params = gather_tree_to_host(params, self._repl_sharding)
        # Flat dp-padded layouts -> canonical shapes (host-side numpy).
        if self.is_zero:
            opt_state = self.optimizer.canonicalize_opt_host(opt_state)
        if self.is_fsdp:
            params = self.zero3.unshard_host(params)
            opt_state = self.zero3.canonicalize_opt_host(opt_state)
        if self._sharded_update is not None:
            opt_state = self._sharded_update.canonicalize_opt_host(
                opt_state)
        to_np = lambda t: jax.tree.map(np.asarray, t)
        tree = {"params": to_np(params), "opt_state": to_np(opt_state),
                "step": np.int64(state.step)}
        if comp_state is not None:
            tree["comp_state"] = to_np(comp_state)
        return tree

    def params_to_host(self, state: TrainState) -> dict:
        """Canonical host numpy params only — the snapshot surface the
        weight-streaming publisher (tpu_ddp/publish/) feeds on every
        ``publish_every`` steps. A params-only subset of
        :meth:`state_to_host`: optimizer/compression state never
        crosses the train→serve boundary."""
        params = state.params
        if self.mesh is not None and self.is_fsdp:
            from tpu_ddp.utils.checkpoint import gather_tree_to_host
            params = gather_tree_to_host(params, self._repl_sharding)
        if self.is_fsdp:
            params = self.zero3.unshard_host(params)
        return jax.tree.map(np.asarray, params)

    def attach_publisher(self, publisher) -> None:
        """Hook a :class:`tpu_ddp.publish.Publisher` into the training
        loop: ``train_epoch`` calls ``publisher.after_step`` once per
        step (publish on cadence, then block on the staleness gate)."""
        self._publisher = publisher

    def state_from_host(self, host: dict) -> TrainState:
        """Place a canonical host tree onto THIS trainer's mesh, laid
        out by its :meth:`sharding_plan` — the other half of
        :meth:`state_to_host`, shared by checkpoint restore and live
        resharding. The source's world size is irrelevant: flat layouts
        re-partition for this trainer's dp from canonical shapes."""
        from tpu_ddp.parallel.redistribute import broadcast_shardings
        plan = self.sharding_plan()
        params = host["params"]
        opt_state = host["opt_state"]
        if self.is_zero:
            opt_state = self.optimizer.flatten_opt(opt_state)
        if self.is_fsdp:
            params = self.zero3.shard_params(params)
            opt_state = self.zero3.flatten_opt(opt_state)
        if self._sharded_update is not None:
            opt_state = self._sharded_update.flatten_opt(opt_state)
        if self.mesh is not None:
            params = jax.device_put(
                params,
                broadcast_shardings(self.mesh, plan.param_specs, params))
            opt_state = jax.device_put(
                opt_state,
                broadcast_shardings(self.mesh, plan.opt_specs, opt_state))
        comp_state = (self._adopt_comp_host(host.get("comp_state"))
                      if self._comp_stateful else None)
        return TrainState(params=params, opt_state=opt_state,
                          step=int(host.get("step", 0)),
                          comp_state=comp_state)

    def _adopt_comp_host(self, comp_host):
        """Adopt a host-form compression carry if its layout matches
        this trainer's template; otherwise reset it (zero residual,
        fresh seed) — the residual is an optimization accelerator, so a
        reset costs a few re-absorbed quantization errors, never
        correctness."""
        template = self._comp_template
        ok = comp_host is not None
        if ok:
            try:
                t_leaves, t_def = jax.tree.flatten(template)
                h_leaves, h_def = jax.tree.flatten(comp_host)
                ok = (t_def == h_def
                      and all(tuple(t.shape) == tuple(np.shape(h))
                              and t.dtype == np.asarray(h).dtype
                              for t, h in zip(t_leaves, h_leaves)))
            except (TypeError, ValueError):
                ok = False
        if not ok:
            if comp_host is not None:
                import warnings
                warnings.warn(
                    "compression carry does not match this trainer's "
                    "layout (different dp or residual shape); resetting "
                    "the error-feedback residual.", stacklevel=3)
            comp_host = self.compressor.init_state(
                self._params_template(), self._dp, seed=self.config.seed)
        if self.mesh is not None:
            comp_host = jax.device_put(comp_host, self._comp_shardings())
        return comp_host

    def rebind_mesh(self, mesh: Mesh) -> None:
        """Re-resolve every mesh-derived surface against a NEW mesh —
        the trainer half of a membership change. The flat ZeRO/FSDP
        layouts, the compression carry template, the batch/replicated
        shardings, the jitted train/eval steps, and the memoized
        K-step / eval closures are all functions of the mesh; rebuild
        or drop each so the next dispatch traces against the new world.
        State placement is NOT done here — pull it through
        :meth:`state_to_host` before the old mesh dies and
        :meth:`state_from_host` after this rebind."""
        self.mesh = mesh
        self._dp = mesh.shape[DATA_AXIS] if mesh is not None else 1
        self._guard_axis = (
            DATA_AXIS if mesh is not None
            and canonical_strategy(self.strategy_name) != "none" else None)
        if self.is_zero:
            from tpu_ddp.parallel.zero import ZeRO1
            self.optimizer = ZeRO1(self.optimizer.inner, DATA_AXIS,
                                   self._dp,
                                   template=self._params_template())
        if self.is_fsdp:
            from tpu_ddp.parallel.zero import ZeRO3
            self.zero3 = ZeRO3(self.zero3.inner, DATA_AXIS, self._dp,
                               template=self._params_template())
        if self._comp_active and self._dp < 2:
            # Compression needs a dp>1 collective to compress; a world
            # shrunk to one data shard degrades to the no-op (same
            # contract as construction-time).
            import warnings
            warnings.warn(
                "mesh rebind left dp=1; gradient compression disabled.",
                stacklevel=2)
            from tpu_ddp.parallel.compress import get_compressor
            self.compressor = get_compressor("none")
            self._comp_active = self._comp_stateful = False
            self._comp_template = self._comp_specs = None
        elif self._comp_stateful:
            self._comp_template = self.compressor.init_state(
                self._params_template(), self._dp, abstract=True)
            self._comp_specs = self.compressor.state_specs(
                self._comp_template)
        if self._overlap_active and (mesh is None or self._dp < 2):
            # Bucketed overlap needs a dp>1 collective; a world shrunk
            # to one data shard degrades to the unbucketed path (same
            # contract as construction-time). Safe mid-run: rebinds are
            # bracketed by the state_to_host/state_from_host canonical
            # round-trip, which re-lays-out the optimizer state.
            import warnings
            warnings.warn(
                "mesh rebind left dp=1; bucketed overlap disabled.",
                stacklevel=2)
            self._overlap_active = False
            self._overlap = self._sharded_update = None
        elif self._overlap_active:
            self._build_overlap()
        if mesh is not None:
            self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
            self._repl_sharding = NamedSharding(mesh, P())
            self._param_put_sharding = (
                NamedSharding(mesh, P(DATA_AXIS)) if self.is_fsdp
                else self._repl_sharding)
        self._train_step = self._build_train_step()
        self._eval_step = self._build_eval_step()
        # Memoized mesh-bound closures: stale against the new world.
        for attr in ("_multi_step_cache", "_sharded_eval",
                     "_materialize_fn"):
            if hasattr(self, attr):
                delattr(self, attr)

    def save_checkpoint(self, directory: str, state: TrainState,
                        keep_last: int | None = None,
                        background: bool = False) -> str | None:
        """Write ``state`` at its step; only process 0 writes (state under
        DP is replicated). Returns the path (None on non-zero processes).

        ``background=True`` snapshots to host synchronously, then hands
        serialization + disk I/O to a writer thread
        (utils/checkpoint.py:AsyncCheckpointWriter) — call
        :meth:`wait_for_checkpoints` before reading the file back or
        exiting. Any gather collectives for sharded state still run
        synchronously on every process (inside state_to_host)."""
        # Checkpoints hold CANONICAL shapes, never the flat dp-padded
        # layout — so they restore at any dp size or into any strategy.
        tree = self.state_to_host(state)
        if jax.process_index() != 0:
            return None
        # The layout contract rides next to the checkpoints, so a
        # restoring trainer of a different world size can check
        # compatibility before touching the tensors.
        self.sharding_plan().save(directory)
        from tpu_ddp.utils import checkpoint as ckpt
        if background:
            if not hasattr(self, "_async_writer"):
                self._async_writer = ckpt.AsyncCheckpointWriter()
            return self._async_writer.submit(directory, tree, state.step,
                                             keep_last=keep_last)
        return ckpt.save_checkpoint(directory, tree, step=state.step,
                                    keep_last=keep_last)

    def wait_for_checkpoints(self) -> None:
        """Block until any background checkpoint write is durable."""
        writer = getattr(self, "_async_writer", None)
        if writer is not None:
            writer.wait()

    def restore_checkpoint(self, directory: str,
                           step: int | None = None) -> TrainState:
        """Load a checkpoint (latest by default) placed like
        :meth:`init_state` places fresh state. Checkpoints hold CANONICAL
        shapes; sharded strategies re-flatten for THIS trainer's dp, so
        a checkpoint moves freely between dp sizes and strategies.

        ``step=None`` restores the newest checkpoint that passes digest
        verification: a corrupt newest checkpoint is quarantined to
        ``step_N.corrupt`` and the previous one is tried
        (resilience/integrity.py) — so a host preempted mid-fsync costs
        one checkpoint interval, not the run. An explicit ``step``
        bypasses the fallback (you asked for THAT checkpoint; restore
        still digest-verifies it and raises CheckpointCorruptError).

        Restore is routed through the saved :class:`ShardingPlan` when
        one rides next to the checkpoints: the saving world's layout is
        checked against this trainer's, and a strategy mismatch is
        surfaced as an informational warning (canonical shapes restore
        across strategies by design; the warning flags that the move
        was cross-layout, not accidental)."""
        from tpu_ddp.utils import checkpoint as ckpt
        from tpu_ddp.parallel.redistribute import ShardingPlan
        saved_plan = ShardingPlan.load(directory)
        if saved_plan is not None:
            mine = self.sharding_plan()
            if not saved_plan.compatible_with(mine):
                import warnings
                warnings.warn(
                    f"checkpoint was written by layout "
                    f"{saved_plan.strategy!r} {dict(saved_plan.mesh_axes)}"
                    f"; restoring into {mine.strategy!r} "
                    f"{dict(mine.mesh_axes)} via canonical shapes.",
                    stacklevel=2)
        params_t = self._params_template()
        if self.is_zero:
            inner = self.optimizer.inner
        elif self.is_fsdp:
            inner = self.zero3.inner
        else:
            inner = self.optimizer
        opt_t = jax.eval_shape(inner.init, params_t)
        template = {"params": params_t, "opt_state": opt_t,
                    "step": np.int64(0)}

        def _restore(tmpl, drop_extra=()):
            if step is None:
                from tpu_ddp.resilience.integrity import \
                    restore_newest_verified
                restored, _ = restore_newest_verified(
                    directory, tmpl, drop_extra=drop_extra)
                return restored
            restored, _ = ckpt.restore_checkpoint(directory, tmpl, step,
                                                  drop_extra=drop_extra)
            return restored

        # Compression carry: restore it when this trainer carries one
        # and the checkpoint has a MATCHING one; on any mismatch —
        # checkpoint without comp_state, different dp, different
        # residual layout — fall back to the base tree and RESET the
        # carry (zero residual, fresh seed). The error-feedback residual
        # is an optimization accelerator, not model state: resetting
        # costs a few re-absorbed quantization errors, never
        # correctness. Symmetrically, a compression-less trainer drops a
        # checkpoint's comp_state leaves instead of refusing the file.
        comp_state = None
        if self._comp_stateful:
            comp_t = self.compressor.init_state(
                params_t, self._dp, seed=self.config.seed,
                abstract=True)
            try:
                restored = _restore({**template, "comp_state": comp_t})
                comp_state = restored["comp_state"]
            except (KeyError, ValueError):
                import warnings
                warnings.warn(
                    "checkpoint has no matching comp_state (different "
                    "dp, layout, or a pre-compression run); resetting "
                    "the error-feedback residual to zeros.", stacklevel=2)
                restored = _restore(template,
                                    drop_extra=("comp_state",))
                comp_state = self.compressor.init_state(
                    params_t, self._dp, seed=self.config.seed)
        else:
            try:
                restored = _restore(template)
            except (KeyError, ValueError):
                restored = _restore(template,
                                    drop_extra=("comp_state",))
        host = {"params": restored["params"],
                "opt_state": restored["opt_state"],
                "step": restored["step"]}
        if comp_state is not None:
            host["comp_state"] = comp_state
        return self.state_from_host(host)

    # ---- train step ----------------------------------------------------

    def _maybe_normalize(self, images):
        """Fused on-device normalization for raw uint8 batches.

        Transferring uint8 moves 4x fewer bytes over PCIe than host-side
        float32 normalization (host-to-device bandwidth is the bottleneck);
        the arithmetic then fuses into the first conv. Branch is on the
        static dtype, so f32 inputs (the reference-parity host path,
        reference part1/main.py:20-31) compile to a no-op. Constants come
        from ``config.dataset``.
        """
        if images.dtype == jnp.uint8:
            from tpu_ddp.data import normalization_constants
            mean, std = normalization_constants(self.config.dataset)
            x = images.astype(jnp.float32) * (1.0 / 255.0)
            return (x - jnp.asarray(mean)) / jnp.asarray(std)
        return images

    def _loss_terms(self, logits, labels, weights):
        """(loss_for_grad, local_mean) for a (possibly wrap-padded) local
        batch. ``weights`` is 1.0 for real examples, 0.0 for padding
        added by :meth:`put_batch`. The differentiated loss is scaled so
        that mean-of-replica-gradients == the gradient of the GLOBAL
        batch-mean loss regardless of padding: per replica we use
        ``R * sum(w*l) / total`` where ``total = psum(sum(w))`` — the
        mean over R replicas then telescopes to ``sum_all(l)/total``.
        With equal unpadded shards this reduces to the plain local batch
        mean, i.e. the reference's semantics
        (part2/part2b/main.py:124-132) exactly."""
        with jax.named_scope("loss"):
            per_ex = softmax_cross_entropy(logits, labels)
            wsum = jnp.sum(weights * per_ex)
            n_local = jnp.sum(weights)
            if self.mesh is not None:
                n_total = lax.psum(n_local, DATA_AXIS)
                n_replicas = lax.psum(1.0, DATA_AXIS)
                loss_for_grad = n_replicas * wsum / n_total
            else:
                loss_for_grad = wsum / jnp.maximum(n_local, 1.0)
            local_mean = wsum / jnp.maximum(n_local, 1.0)
            return loss_for_grad, local_mean

    def _guarded_apply(self, params, opt_state, loss, grads, apply_fn,
                       extra_bad=None):
        """Run ``apply_fn() -> (new_params, new_opt)`` under the step
        guard: a non-finite loss/grad-norm selects the OLD state back
        (momentum included — the bad step is an exact no-op) and raises
        the jit-side ``skipped`` flag. A healthy step is bit-identical
        to an unguarded one (``where`` on a false predicate is the
        identity). With the guard disabled, just applies. ``extra_bad``
        forwards an upstream badness count to the flag (the overlapped
        int8 path's raw-gradient nonfinite count — see
        resilience/guard.py:nonfinite_flag)."""
        if self.guard is None:
            with jax.named_scope("optimizer"):
                new_params, new_opt = apply_fn()
            return new_params, new_opt, jnp.zeros((), jnp.float32)
        bad = nonfinite_flag(loss, grads, self._guard_axis,
                             extra_bad=extra_bad)
        with jax.named_scope("optimizer"):
            new_params, new_opt = apply_fn()
        return (select_update(bad, params, new_params),
                select_update(bad, opt_state, new_opt),
                bad.astype(jnp.float32))

    def _base_step(self, params, opt_state, images, labels, weights,
                   comp=None):
        images = self._maybe_normalize(images)

        if self._overlap_active:
            return self._overlap_step(params, opt_state, images, labels,
                                      weights, comp)

        if self.is_fsdp:
            if self._comp_active:
                return self._fsdp_compressed_step(
                    params, opt_state, images, labels, weights, comp)

            def loss_fn(flat):
                # all_gather materializes full params transiently; its
                # AD transpose reduce-scatters the cotangent, delivering
                # this worker's SUMMED gradient shard directly.
                p = self.zero3.gather_params(flat)
                return self._loss_terms(self.model.apply(p, images),
                                        labels, weights)

            (_, loss), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # psum_scatter summed over workers; recover the replica mean.
            grads = jax.tree.map(lambda g: g / float(self._dp), grads)
            if self.clip_grad_norm is not None:
                # Flat dp shards hold distinct elements: psum the
                # squared sums over dp for the exact global norm.
                from tpu_ddp.ops.optim import (clip_scale_from_sq,
                                               clip_tree)
                sq = lax.psum(
                    sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)), DATA_AXIS)
                grads = clip_tree(
                    grads, clip_scale_from_sq(sq, self.clip_grad_norm))
            params, opt_state, skipped = self._guarded_apply(
                params, opt_state, loss, grads,
                lambda: self.zero3.apply(params, grads, opt_state))
            return params, opt_state, loss, skipped, None

        def loss_fn(p):
            return self._loss_terms(self.model.apply(p, images),
                                    labels, weights)

        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_comp = None
        if self._comp_active and not self.is_zero:
            # Compressed replicated rungs: the compressor IS the sync.
            # The guard flag must come from the PRE-compression local
            # grads — a NaN can vanish through the int8 cast, and the
            # error-feedback carry must roll back on a skipped step.
            guard_grads = grads
            grads, new_comp = self.compressor.sync_replicated(
                self._comp_kind, grads, comp, DATA_AXIS, self._dp)
        else:
            # Under ZeRO sync_fn is the identity: the optimizer's own
            # reduce_scatter + all_gather pair performs the
            # synchronization.
            with jax.named_scope("grad_sync"):
                grads = self.sync_fn(grads, DATA_AXIS) \
                    if self.mesh is not None else self.sync_fn(grads)
            guard_grads = grads
        if self.is_zero:
            # Clip (if any) happens on the wrapper's dp-scattered slices
            # — the only place the synced gradient values exist. The
            # guard flag, by contrast, must come from the PRE-scatter
            # local grads (sync_fn is identity here) psum'd across dp —
            # a rank-local decision would diverge the replicas.
            if self._comp_active:
                # Compressed ZeRO: the compressor's phase-1 all_to_all
                # replaces the wrapper's psum_scatter, delivering the
                # dp-scattered fp32 MEAN slices apply_scattered expects.
                g_sh, new_comp = self.compressor.scatter_mean(
                    grads, comp, DATA_AXIS, self._dp)
                params, opt_state, skipped = self._guarded_apply(
                    params, opt_state, loss, grads,
                    lambda: self.optimizer.apply_scattered(
                        params, g_sh, opt_state,
                        clip_norm=self.clip_grad_norm))
            else:
                params, opt_state, skipped = self._guarded_apply(
                    params, opt_state, loss, grads,
                    lambda: self.optimizer.apply(
                        params, grads, opt_state,
                        clip_norm=self.clip_grad_norm))
            new_comp = self._comp_rollback(skipped, comp, new_comp)
            return params, opt_state, loss, skipped, new_comp
        if self.clip_grad_norm is not None:
            # Replicated rungs: grads are identical on every replica
            # after sync (compressed or not — the compressed mean is
            # all_gathered, so every replica holds the same bytes), so
            # the local squared sum IS the global one. (Under strategy
            # 'none' each replica clips by its own norm — consistent
            # with that rung's no-sync semantics.)
            from tpu_ddp.ops.optim import clip_scale_from_sq, clip_tree
            with jax.named_scope("clip"):
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))
                grads = clip_tree(
                    grads, clip_scale_from_sq(sq, self.clip_grad_norm))
        params, opt_state, skipped = self._guarded_apply(
            params, opt_state, loss, guard_grads,
            lambda: self.optimizer.apply(params, grads, opt_state))
        new_comp = self._comp_rollback(skipped, comp, new_comp)
        return params, opt_state, loss, skipped, new_comp

    def _overlap_step(self, params, opt_state, images, labels, weights,
                      comp):
        """Replicated rungs with bucketed in-backward sync
        (parallel/overlap.py): the taps' backward rules ARE the sync, so
        no sync_fn runs here. gather_scatter yields full root-mean
        grads and a replicated update; all_reduce/fused yield a
        scattered reduction finished by the sharded update (their
        distinction — per-leaf vs tree-level all-reduce — is about HOW
        the unbucketed collective is issued, which bucketing replaces,
        so under overlap the two rungs compile to the same program).

        Guard semantics: the flag psum (nonfinite_flag) sees every
        device's slice of the synced grads, so a NaN anywhere raises it
        on all replicas even though the scattered layout gives each
        device only its chunk; ``extra_bad`` carries the int8 path's
        raw-gradient nonfinite count, which the quantization cast would
        otherwise hide. A skipped step rolls back the compression carry
        exactly like the unbucketed path."""

        def loss_fn(p):
            return self._loss_terms(self.model.apply(p, images),
                                    labels, weights)

        loss, grads, new_comp, extra_bad = self._overlap.value_and_grad(
            loss_fn, params, comp)
        if self._sharded_update is not None:
            # Clip (if any) happens on the update's payload slices —
            # the chunks tile the mean exactly once across devices, so
            # a psum of slice squared-sums is the exact global norm
            # (ZeRO-1's argument).
            params, opt_state, skipped = self._guarded_apply(
                params, opt_state, loss, grads,
                lambda: self._sharded_update.apply_scattered(
                    params, grads, opt_state,
                    clip_norm=self.clip_grad_norm),
                extra_bad=extra_bad)
        else:
            # The guard must see PRE-clip grads: an inf norm clips the
            # gradient to zeros, hiding itself from the post-clip check.
            guard_grads = grads
            if self.clip_grad_norm is not None:
                # Root-mean grads are replicated: local norm == global.
                from tpu_ddp.ops.optim import (clip_scale_from_sq,
                                               clip_tree)
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads))
                grads = clip_tree(
                    grads, clip_scale_from_sq(sq, self.clip_grad_norm))
            params, opt_state, skipped = self._guarded_apply(
                params, opt_state, loss, guard_grads,
                lambda: self.optimizer.apply(params, grads, opt_state),
                extra_bad=extra_bad)
        new_comp = self._comp_rollback(skipped, comp, new_comp)
        return params, opt_state, loss, skipped, new_comp

    def _comp_rollback(self, skipped, comp, new_comp):
        """A skipped (guarded) step must not consume the compression
        carry: the residual would otherwise absorb a gradient that was
        never applied, and the seed would advance — select the OLD carry
        back so the skip stays an exact no-op."""
        if new_comp is None:
            return None
        return select_update(skipped > 0, comp, new_comp)

    def _fsdp_compressed_step(self, params, opt_state, images, labels,
                              weights, comp):
        """FSDP with a compressed wire: the param all_gather moves
        OUTSIDE the differentiated function, so the gradient arrives as
        full canonical leaves LOCALLY (no f32 reduce_scatter from the AD
        transpose) and the compressor's phase-1 all_to_all performs the
        folded reduce_scatter at the reduced dtype. The parameter
        all_gather itself stays fp32 — parameters, not gradients, and
        out of this layer's scope (docs/DESIGN.md §14)."""
        full = self.zero3.gather_params(params)

        def loss_fn(p):
            return self._loss_terms(self.model.apply(p, images),
                                    labels, weights)

        (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(full)
        g_sh, new_comp = self.compressor.scatter_mean(
            grads, comp, DATA_AXIS, self._dp)
        if self.clip_grad_norm is not None:
            # The scattered mean slices hold distinct elements per
            # device: psum the squared sums for the exact global norm.
            from tpu_ddp.ops.optim import clip_scale_from_sq, clip_tree
            sq = lax.psum(
                sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(g_sh)),
                DATA_AXIS)
            g_sh = clip_tree(g_sh,
                             clip_scale_from_sq(sq, self.clip_grad_norm))
        params, opt_state, skipped = self._guarded_apply(
            params, opt_state, loss, grads,
            lambda: self.zero3.apply(params, g_sh, opt_state))
        new_comp = self._comp_rollback(skipped, comp, new_comp)
        return params, opt_state, loss, skipped, new_comp

    def _build_train_step(self) -> Callable:
        # The step returns (params, opt_state, loss, fused) where
        # ``fused`` stacks [loss, skipped] into ONE small f32 array —
        # so harvesting a step's scalars costs a single device fetch
        # (the pre-round-6 loop fetched loss and the skip flag
        # separately, two round-trips per iteration). ``loss`` keeps
        # its public per-replica shape for train_step's callers.
        #
        # Elastic runs give up input donation: when a peer dies
        # mid-collective the step's OUTPUT buffers hold error events,
        # so the only live state a survivor can carry across the
        # membership change is the step's INPUT — which donation would
        # have invalidated. One transient extra params+opt copy is the
        # price of restart-free resharding (docs/DESIGN.md §17).
        from tpu_ddp.resilience.elastic import elastic_env_active
        keep_inputs = elastic_env_active()
        don2 = () if keep_inputs else (0, 1)
        don3 = () if keep_inputs else (0, 1, 2)
        if self.mesh is None:
            @program(DDP_TRAIN_STEP)
            def base(params, opt_state, images, labels, weights):
                params, opt_state, loss, skipped, _ = self._base_step(
                    params, opt_state, images, labels, weights)
                fused = jnp.stack([loss.astype(jnp.float32), skipped])
                return params, opt_state, loss, fused

            return jax.jit(base, donate_argnums=don2)

        opt_spec = self._opt_spec()
        param_spec = self._param_spec()

        if self._comp_stateful:
            # Stateful compression (int8): the carry threads through the
            # jitted step as a third donated argument — the residual is
            # param-sized, so donation keeps one buffer alive, not two.
            @program(DDP_TRAIN_STEP)
            def comp_body(params, opt_state, comp, images, labels,
                          weights):
                params, opt_state, loss, skipped, comp = self._base_step(
                    params, opt_state, images, labels, weights, comp)
                fused = jnp.stack([loss.astype(jnp.float32),
                                   skipped]).reshape(1, 2)
                return params, opt_state, comp, loss.reshape(1), fused

            mapped = jax.shard_map(
                comp_body,
                mesh=self.mesh,
                in_specs=(param_spec, opt_spec, self._comp_specs,
                          P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=(param_spec, opt_spec, self._comp_specs,
                           P(DATA_AXIS), P(DATA_AXIS)),
                check_vma=False,
            )
            return jax.jit(mapped, donate_argnums=don3)

        @program(DDP_TRAIN_STEP)
        def sharded_body(params, opt_state, images, labels, weights):
            params, opt_state, loss, skipped, _ = self._base_step(
                params, opt_state, images, labels, weights)
            # Per-replica scalar -> (1,) so out_spec P(dp) stacks to (dp,):
            # each node keeps printing ITS shard's running loss, as in the
            # reference (every node prints locally, part2b/main.py:134-139).
            # The fused [loss, skipped] pair travels the same way as a
            # (1, 2) row -> global (dp, 2) (replicas agree on the flag by
            # construction except under strategy 'none').
            fused = jnp.stack([loss.astype(jnp.float32),
                               skipped]).reshape(1, 2)
            return params, opt_state, loss.reshape(1), fused

        mapped = jax.shard_map(
            sharded_body,
            mesh=self.mesh,
            in_specs=(param_spec, opt_spec, P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=(param_spec, opt_spec, P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=don2)

    def lower_train_step(self, state: TrainState, images, labels,
                         weights):
        """``jit.lower`` the compiled train step with ``state`` —
        signature-agnostic (the stateful-compression step takes the
        carry as a third argument). Used by the HLO inspection tooling
        (scripts/comm_volume.py, utils/hlo_comm.py)."""
        if self._comp_stateful:
            return self._train_step.lower(
                state.params, state.opt_state, state.comp_state,
                images, labels, weights)
        return self._train_step.lower(state.params, state.opt_state,
                                      images, labels, weights)

    def build_multi_step(self, k: int):
        """Compile a K-steps-per-dispatch train call: ``fn(state, xs,
        ys, ws) -> (state, losses)`` where the batch arrays carry a
        leading ``k`` axis and ``losses`` stacks the per-step losses.

        The TPU-first lever for small models: one ``lax.scan`` over K
        full optimizer steps amortizes per-call dispatch/host overhead
        K-fold (a VGG-11/CIFAR step at batch 256 is dispatch-bound on a
        single chip — measured ~6 ms dispatch vs ~3 ms compute). Each
        scanned step is bit-identical to :meth:`train_step`'s body
        (tested in tests/test_engine.py); the reference has no
        counterpart (its loop is host-driven by construction,
        part1/main.py:65-77).
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        # Memoized per k: each build creates fresh closures, so jax.jit's
        # own cache can never hit across builds — without this, an
        # E-epoch grouped-K run re-COMPILES the scan every epoch
        # (_train_epoch_multi builds per epoch; surfaced by the
        # autotuner's repeated-epoch trials). Everything the closures
        # capture (mesh, specs, _comp_stateful, the step body) is fixed
        # at construction, so reuse is sound.
        cache = getattr(self, "_multi_step_cache", None)
        if cache is None:
            cache = self._multi_step_cache = {}
        if k in cache:
            return cache[k]

        def scan_body(params, opt_state, comp, xs, ys, ws):
            def step(carry, xyw):
                p, o, c = carry
                p, o, loss, skipped, c = self._base_step(p, o, *xyw,
                                                         comp=c)
                return (p, o, c), (loss, skipped)

            (params, opt_state, comp), (losses, skips) = lax.scan(
                step, (params, opt_state, comp), (xs, ys, ws))
            return params, opt_state, comp, losses, skips

        # As in _build_train_step, the per-step [loss, skipped] pairs are
        # fused into ONE device array — (k, 2) without a mesh, global
        # (k, dp, 2) with one — so harvesting a whole K-group costs a
        # single fetch.
        if self.mesh is None:
            @program(DDP_TRAIN_MULTI_STEP)
            def body(params, opt_state, xs, ys, ws):
                params, opt_state, _, losses, skips = scan_body(
                    params, opt_state, None, xs, ys, ws)
                fused = jnp.stack([losses.astype(jnp.float32), skips],
                                  axis=-1)
                return params, opt_state, losses, fused

            fn = jax.jit(body, donate_argnums=(0, 1))
        elif self._comp_stateful:
            @program(DDP_TRAIN_MULTI_STEP)
            def comp_sharded_body(params, opt_state, comp, xs, ys, ws):
                params, opt_state, comp, losses, skips = scan_body(
                    params, opt_state, comp, xs, ys, ws)
                fused = jnp.stack(
                    [losses.astype(jnp.float32).reshape(k, 1),
                     skips.reshape(k, 1)], axis=-1)  # (k, 1, 2)
                return (params, opt_state, comp, losses.reshape(k, 1),
                        fused)

            b = P(None, DATA_AXIS)
            mapped = jax.shard_map(
                comp_sharded_body, mesh=self.mesh,
                in_specs=(self._param_spec(), self._opt_spec(),
                          self._comp_specs, b, b, b),
                out_specs=(self._param_spec(), self._opt_spec(),
                           self._comp_specs, b, P(None, DATA_AXIS)),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=(0, 1, 2))
        else:
            @program(DDP_TRAIN_MULTI_STEP)
            def sharded_body(params, opt_state, xs, ys, ws):
                params, opt_state, _, losses, skips = scan_body(
                    params, opt_state, None, xs, ys, ws)
                fused = jnp.stack(
                    [losses.astype(jnp.float32).reshape(k, 1),
                     skips.reshape(k, 1)], axis=-1)  # (k, 1, 2)
                return (params, opt_state, losses.reshape(k, 1), fused)

            b = P(None, DATA_AXIS)
            mapped = jax.shard_map(
                sharded_body, mesh=self.mesh,
                in_specs=(self._param_spec(), self._opt_spec(), b, b, b),
                out_specs=(self._param_spec(), self._opt_spec(), b,
                           P(None, DATA_AXIS)),
                check_vma=False)
            fn = jax.jit(mapped, donate_argnums=(0, 1))

        def run(state: TrainState, xs, ys, ws=None):
            if ws is None:
                ws = jnp.ones(xs.shape[:2], jnp.float32)
            if self._comp_stateful:
                params, opt_state, comp, losses, fused = fn(
                    state.params, state.opt_state, state.comp_state,
                    xs, ys, ws)
            else:
                comp = state.comp_state
                params, opt_state, losses, fused = fn(
                    state.params, state.opt_state, xs, ys, ws)
            # The fused bundle rides on the side (run keeps its public
            # (state, losses) shape); the epoch loop harvests it for
            # loss/skip accounting with one fetch.
            self._last_fused = fused
            return TrainState(params, opt_state, state.step + k,
                              comp), losses

        cache[k] = run
        return run

    def put_batches(self, images_k, labels_k):
        """Stage K batches for :meth:`build_multi_step`: (k, B, ...)
        host arrays -> device arrays with the batch axis sharded over dp
        (k is a leading scan axis, replicated)."""
        images_k = np.asarray(images_k)
        labels_k = np.asarray(labels_k)
        weights_k = np.ones(labels_k.shape, np.float32)
        if self.mesh is not None:
            # Input is this PROCESS's shard of each per-step batch (the
            # put_batch contract); check divisibility against the local
            # slot count, as put_batch does. No wrap-padding here: the
            # scan axis makes ragged-final-batch handling ambiguous —
            # feed the ragged tail through train_step instead.
            n_slots = self.mesh.shape[DATA_AXIS]
            local_slots = max(n_slots // max(jax.process_count(), 1), 1)
            if labels_k.shape[1] % local_slots:
                raise ValueError(
                    f"per-process per-step batch {labels_k.shape[1]} "
                    f"not divisible by local dp slots {local_slots}")
        if self.mesh is None:
            return (jnp.asarray(images_k), jnp.asarray(labels_k),
                    jnp.asarray(weights_k))
        from tpu_ddp.parallel.mesh import put_sharded
        sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
        return (put_sharded(images_k, sh), put_sharded(labels_k, sh),
                put_sharded(weights_k, sh))

    def _dispatch_step(self, state: TrainState, images, labels, weights):
        """Dispatch one jitted step; returns ``(state, loss, fused)``
        without any host synchronization — everything is a device-array
        future. ``fused`` is the ONE-fetch [loss, skipped] bundle
        (see _build_train_step)."""
        if weights is None:
            weights = jnp.ones((images.shape[0],), jnp.float32)
        if self._comp_stateful:
            params, opt_state, comp, loss, fused = self._train_step(
                state.params, state.opt_state, state.comp_state,
                images, labels, weights)
        else:
            comp = state.comp_state
            params, opt_state, loss, fused = self._train_step(
                state.params, state.opt_state, images, labels, weights)
        # Stashed for last_step_skipped (the public train_step keeps
        # its (state, loss) shape).
        self._last_fused = fused
        return TrainState(params, opt_state, state.step + 1,
                          comp), loss, fused

    def train_step(self, state: TrainState, images, labels,
                   weights=None) -> tuple:
        """One optimization step; returns (state, loss).

        With a mesh, ``loss`` is the per-replica loss vector (one entry per
        dp slot); without, a scalar. ``weights`` defaults to all-ones (use
        :meth:`put_batch`, which builds and shards them).
        """
        state, loss, _ = self._dispatch_step(state, images, labels,
                                             weights)
        return state, loss

    def train_step_async(self, state: TrainState, images, labels,
                         weights=None) -> tuple:
        """Like :meth:`train_step` but returns ``(state, fused)`` where
        ``fused`` is the step's [loss, skipped] device bundle — the
        handle the async epoch loop pushes onto its
        :class:`~tpu_ddp.train.pipeline.DispatchPipeline` and harvests
        with ONE device fetch (:meth:`_materialize_fused`)."""
        state, _, fused = self._dispatch_step(state, images, labels,
                                              weights)
        return state, fused

    def _materialize_fused(self, fused) -> tuple[float, bool]:
        """(local_loss, skipped) from a single-step fused bundle — ONE
        host fetch. With a mesh the global array is (dp, 2); this
        process's first addressable row is [its shard's loss, the
        psum-agreed skip flag] — the same local-shard read pattern the
        old loop used for the loss alone."""
        if self.mesh is not None:
            row = np.ravel(np.asarray(fused.addressable_shards[0].data))
        else:
            row = np.ravel(np.asarray(fused))
        return float(row[0]), bool(row[1] > 0)

    def last_step_skipped(self) -> bool:
        """True iff the most recent train_step's update was skipped by
        the non-finite guard (resilience/guard.py). Reads the fused
        [loss, skipped] bundle — ``skipped`` is the LAST element of the
        flattened local view for every bundle shape: (2,) single-step
        without a mesh, local (local_dp, 2) with one, (k, local_dp, 2)
        for a K-group (where the last row is the group's final step)."""
        arr = getattr(self, "_last_fused", None)
        if arr is None:
            return False
        flat = np.ravel(np.asarray(
            arr.addressable_shards[0].data
            if hasattr(arr, "addressable_shards") else arr))
        return bool(flat[-1] > 0)

    # ---- data placement ------------------------------------------------

    def put_batch(self, images, labels, weights=None):
        """Place a host batch onto the mesh: batch axis sharded over dp.

        Returns ``(images, labels, weights)``. When the batch size is not
        divisible by the number of dp slots (the ragged final batch of a
        ``drop_last=False`` epoch, reference part1/main.py:36-41), the batch
        is wrap-padded to divisibility and the padding rows get weight 0 —
        the weighted loss in :meth:`_base_step` makes them exact no-ops.

        ``weights`` (optional) are per-example validity weights from the
        loader (process-sharded eval marks sampler wrap-padding rows 0);
        default all-ones. Divisibility padding appends further zeros.

        Single process: ``images``/``labels`` are the global batch. Multi
        process: they are this process's shard of the global batch (the L4
        sampler already sharded them — shard sizes are symmetric across
        ranks by DistributedSampler padding), assembled into a global array.
        """
        images = np.asarray(images)
        labels = np.asarray(labels)
        weights = (np.ones((len(labels),), np.float32)
                   if weights is None
                   else np.asarray(weights, np.float32))
        if self.mesh is None:
            return jnp.asarray(images), jnp.asarray(labels), \
                jnp.asarray(weights)
        n_slots = self.mesh.shape[DATA_AXIS]
        local_slots = max(n_slots // max(jax.process_count(), 1), 1)
        if len(labels) % local_slots:
            pad = local_slots - len(labels) % local_slots
            sel = np.arange(pad) % len(labels)
            images = np.concatenate([images, images[sel]])
            labels = np.concatenate([labels, labels[sel]])
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
        from tpu_ddp.parallel.mesh import put_sharded
        return (put_sharded(images, self._batch_sharding),
                put_sharded(labels, self._batch_sharding),
                put_sharded(weights, self._batch_sharding))

    # ---- epoch loop (reference train_model, part1/main.py:52-93) -------

    def _raise_membership_change(self, exc, elastic, state, epoch, it,
                                 heartbeat, wait_s: float = 60.0):
        """When a step died because a PEER died, convert the wreckage
        into a :class:`~tpu_ddp.resilience.elastic.MembershipChange`.

        A lost rank surfaces on survivors as a ``JaxRuntimeError`` from
        the in-flight collective (gloo: "Connection closed by peer").
        That alone does not prove a membership change — a genuinely
        broken network should still crash — so this waits up to
        ``wait_s`` for the launcher (or the departing rank itself) to
        confirm one via the protocol directory, beating the heartbeat
        meanwhile so the watchdog knows the survivor is alive. Confirmed
        -> raise MembershipChange carrying ``state`` (the failed step's
        INPUT, the last fully-materialized tree — see the no-donation
        note in _build_train_step); unconfirmed -> return, and the
        caller re-raises the original error."""
        if elastic is None:
            return
        if not isinstance(exc, jax.errors.JaxRuntimeError):
            return
        from tpu_ddp.resilience.elastic import MembershipChange
        from tpu_ddp.resilience.watchdog import touch_heartbeat
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            if elastic.changed():
                raise MembershipChange(
                    membership=elastic.read(), state=state,
                    epoch=epoch, next_iter=it) from exc
            if heartbeat is not None:
                touch_heartbeat(heartbeat[0], heartbeat[1], state.step)
            time.sleep(0.1)

    def train_epoch(
        self,
        state: TrainState,
        batches,
        epoch: int = 0,
        log: Callable[[str], None] = print,
        ckpt_dir: str | None = None,
        start_iter: int = 0,
    ) -> tuple[TrainState, dict]:
        """``start_iter`` > 0 skips that many leading batches — the
        mid-epoch resume path (the checkpoint's step places the run
        ``step % iters_per_epoch`` batches into its epoch; replaying the
        prefix would double-train those examples and inflate step)."""
        cfg = self.config
        timer = IterationTimer(cfg.timing_first_iter, cfg.timing_last_iter)
        window = _LossWindow(cfg, self.metrics, timer, epoch, log)
        # Advance past the resumed prefix BEFORE prefetch wraps the
        # stream, so skipped batches are never processed or transferred.
        if start_iter:
            import itertools
            batches = itertools.islice(iter(batches), start_iter, None)
        # Resilience hooks (resilience/): chaos fault injection from env
        # and the per-rank heartbeat the launcher's watchdog monitors.
        from tpu_ddp.resilience.chaos import (FaultInjector,
                                              chaos_env_active)
        from tpu_ddp.resilience.watchdog import (heartbeat_from_env,
                                                 touch_heartbeat)
        from tpu_ddp.resilience.elastic import ElasticController
        injector = FaultInjector.from_env()
        heartbeat = heartbeat_from_env()
        # Elastic membership watch (resilience/elastic.py): a cheap
        # mtime poll per iteration; on a membership epoch bump the loop
        # drains its in-flight window and hands the LIVE state up via
        # MembershipChange — parts/common.py rebuilds the world and
        # resumes this epoch at ``next_iter``.
        elastic = ElasticController.from_env()
        # K-steps-per-dispatch path (cfg.steps_per_dispatch > 1): groups
        # of K uniform batches run as ONE jitted scan (build_multi_step).
        # Anything that needs per-step host control forces the per-step
        # path: in-loop checkpoint/invariant cadences, the fault-
        # injection drills (they must fire at an exact step), and
        # device_prefetch (its overlap is a per-step transfer pipeline;
        # composing it with grouped dispatch is not implemented).
        if (cfg.steps_per_dispatch > 1 and not cfg.ckpt_every_iters
                and not cfg.check_replicas_every
                and not cfg.device_prefetch
                and not chaos_env_active()
                and elastic is None):
            return self._train_epoch_multi(state, batches, timer,
                                           window, start_iter=start_iter,
                                           heartbeat=heartbeat)
        # With device_prefetch > 0 upcoming batches' transfers are already
        # in flight when the step runs (tpu_ddp/data/prefetch.py); the
        # timer still brackets the same loop body as the reference
        # (part1/main.py:65-66 starts its clock after the batch fetch).
        # Prefetch is disabled only for faults that must poison a batch
        # HOST-SIDE on an exact step, before its transfer (nan-grad);
        # passive injectors (slow-rank, hard-exit, ...) compose with it.
        use_prefetch = (cfg.device_prefetch > 0
                        and not injector.poisons_batches)
        stream = prefetch_to_device(batches, self.put_batch,
                                    cfg.device_prefetch) \
            if use_prefetch else batches
        # Async dispatch window (train/pipeline.py): up to cfg.
        # dispatch_depth steps stay in flight; losses, guard flags,
        # heartbeats and the checkpoint/replica cadences are all driven
        # from HARVESTED (in-order) results via on_harvest below — no
        # aux subsystem forces a device sync. Active chaos forces the
        # synchronous window: faults must land on exact steps, and a
        # poisoned step's divergence must surface before the next
        # dispatch (docs/DESIGN.md §13).
        #
        # Multi-process runs force it too when an in-loop cadence bears
        # cross-host collectives: save_checkpoint gathers sharded state
        # (ZeRO/FSDP) and check_replica_consistency process_allgathers
        # digests, and both snapshot the CURRENT `state`. Harvest timing
        # is per-process (is_ready polling), so at depth > 0 process A
        # could run a step-N cadence between dispatching steps N+1 and
        # N+2 while process B runs it after N+2 — the collectives
        # enqueue in different orders relative to the train steps'
        # psums (deadlock risk) and contribute different-step states
        # (mixed-version checkpoints, spurious ReplicaDivergenceError).
        # Depth 0 pins every cadence to the same loop position with the
        # same-step state on all processes — the same reasoning that
        # routes these cadences off the grouped-K path above.
        collective_cadence = bool(
            (ckpt_dir and cfg.ckpt_every_iters)
            or (cfg.check_replicas_every and self.mesh is not None))
        # Elastic membership also forces the synchronous window: a
        # survivor of a mid-collective peer death can only carry the
        # last FULLY-MATERIALIZED state across the reshard, and at
        # depth 0 that is exactly the previous iteration's output
        # (kept live by the no-donation elastic step build).
        depth = (0 if chaos_env_active()
                 or (collective_cadence and jax.process_count() > 1)
                 or elastic is not None
                 else cfg.dispatch_depth)
        pipe = DispatchPipeline(depth)

        def on_harvest(harv_it, harv_step, fused):
            with span("tpu_ddp.train.harvest", it=harv_it):
                harvested(harv_it, harv_step,
                          self._materialize_fused(fused))

        def harvested(harv_it, harv_step, result):
            local_loss, skipped = result
            window.account(harv_it, local_loss, harv_step)
            if self.guard is not None:
                # Raises TrainingDivergedError after K consecutive skips
                # — BEFORE the checkpoint cadence below, so the last
                # checkpoint on disk predates the divergence being
                # acted on. Under async dispatch the raise happens at
                # HARVEST, i.e. at most `depth` steps after the bad
                # step ran (the delayed-divergence contract).
                self.guard.record(harv_step, skipped, local_loss)
            if heartbeat is not None:
                # The beat carries the last HARVESTED step: a healthy
                # async window still beats at least once per `depth`
                # steps, far inside any stall deadline.
                touch_heartbeat(heartbeat[0], heartbeat[1], harv_step)
            # Aux subsystems (no reference equivalent — SURVEY.md §5):
            # mid-epoch checkpoints, replica-invariant check, fault hook.
            # Cadences test the harvested step; the state they act on is
            # the CURRENT one — up to `depth` steps ahead, which is safe
            # SINGLE-process (a skipped step is an exact no-op on the
            # state, and the checkpoint is stamped with its own step).
            # Multi-process, these cadences are cross-host collectives,
            # so the depth guard above already forced depth 0 and the
            # state here is exactly harv_step's on every process.
            if (ckpt_dir and cfg.ckpt_every_iters
                    and harv_step % cfg.ckpt_every_iters == 0):
                self.save_checkpoint(ckpt_dir, state)
            if (cfg.check_replicas_every and self.mesh is not None
                    and harv_step % cfg.check_replicas_every == 0):
                if self.is_fsdp:
                    # FSDP has NO replicated parameter leaves — there is
                    # no redundancy to cross-check, and silently passing
                    # would fake coverage. Warn once and skip.
                    if not getattr(self, "_warned_fsdp_check", False):
                        self._warned_fsdp_check = True
                        log("[invariants] check_replicas_every has no "
                            "replicated leaves to check under fsdp; "
                            "skipping")
                else:
                    from tpu_ddp.utils.invariants import \
                        check_replica_consistency
                    check_replica_consistency(state.params)
            # Post-step faults: hard-exit / corrupt-ckpt (and the legacy
            # TPU_DDP_FAIL_AT_STEP knob) fire AFTER the step's save, so
            # a crash-step checkpoint is always on disk. (Chaos always
            # runs at depth 0, so harv_step is the just-completed step.)
            injector.after_step(harv_step, ckpt_dir)
            # Weight streaming (tpu_ddp/publish/): publish on cadence,
            # then block on the staleness gate. Snapshots the CURRENT
            # state like the checkpoint cadence above — same depth
            # reasoning applies.
            if self._publisher is not None:
                self._publisher.after_step(state, harv_step)

        for it, item in enumerate(
                spanned(stream, "tpu_ddp.train.data_next"),
                start=start_iter):
            if cfg.max_iters is not None and it >= cfg.max_iters:
                break
            if elastic is not None and elastic.changed():
                # Batch `it` has been pulled but NOT trained on; the
                # resumed epoch replays exactly from here. Drain first:
                # every dispatched step must land in `state` (and the
                # guard/loss window) before the world is torn down.
                pipe.drain()
                from tpu_ddp.resilience.elastic import MembershipChange
                raise MembershipChange(
                    membership=elastic.read(), state=state,
                    epoch=epoch, next_iter=it)
            if injector.active:
                # Pre-step faults for the step producing state.step + 1:
                # nan-grad poisons THIS rank's shard of the batch (sync
                # spreads the NaNs; the guard then skips on all ranks),
                # stalled-step/slow-rank sleep here.
                if injector.before_step(state.step + 1):
                    item = (FaultInjector.poison_images(item[0]),) \
                        + tuple(item[1:])
            # The reference's timing protocol is per-iteration
            # synchronous (clock stops after block_until_ready,
            # part1/main.py:86-91); iterations inside the timing window
            # therefore dispatch-and-wait even at depth > 0 — and at
            # depth > 0 the pipeline books their (pre-blocked, ~free)
            # deliveries under sync_deliveries, not the async window's
            # forced_syncs/host_gap_ms. Depth 0 submits sync throughout
            # and keeps its per-step forced-sync accounting: that IS
            # the synchronous baseline the depth sweep measures.
            sync_iter = depth == 0 or it <= cfg.timing_last_iter
            timer.start()
            # A second live reference to the step's input would defeat
            # buffer donation (the runtime copies a donated buffer that
            # is still referenced elsewhere); only the elastic path —
            # whose steps are built non-donating — carries it.
            prev_state = state if elastic is not None else None
            try:
                if use_prefetch:
                    x, y, w = item
                else:
                    with span("tpu_ddp.train.put_batch"):
                        x, y, w = self.put_batch(*item)
                with span("tpu_ddp.train.dispatch", it=it,
                          step=state.step):
                    state, fused = self.train_step_async(state, x, y, w)
                if sync_iter:
                    # Force completion before stopping the clock — the
                    # JAX-correct analogue of the reference's synchronous
                    # CPU timing.
                    jax.block_until_ready(fused)
                timer.stop(it)
                pipe.submit(
                    fused,
                    lambda f, i=it, s=state.step: on_harvest(i, s, f),
                    sync=sync_iter)
            except Exception as e:  # noqa: BLE001 — filtered below
                # A peer dying mid-collective surfaces HERE (the gloo
                # all-reduce fails on the survivor), usually before the
                # loop-top membership poll can see the departure note.
                # If the launcher confirms a membership change, this
                # step never happened: hand up the last materialized
                # state and replay batch `it` after the reshard.
                self._raise_membership_change(
                    e, elastic, prev_state, epoch, it, heartbeat)
                raise
        pipe.drain()
        return state, window.epoch_stats(pipeline=pipe.stats())

    def _train_epoch_multi(self, state, batches, timer, window,
                           start_iter, heartbeat=None):
        """Epoch loop with K optimizer steps per dispatch.

        Groups of K same-shape, slot-divisible host batches run through
        :meth:`build_multi_step`'s scanned call (bit-equal to K single
        steps — tested); ragged tails fall back to the per-step path.
        Loss-print cadence and the iteration-window timer keep the
        reference's semantics via the shared ``_LossWindow`` (per-
        dispatch time attributed evenly to its K iterations).

        The async dispatch window composes: up to ``cfg.dispatch_depth
        // K`` GROUPS stay in flight (each group is K steps, so the
        harvest lag stays ≤ dispatch_depth steps; a depth below K means
        synchronous dispatch). Each group's losses + skip flags arrive
        as ONE fused (K, [dp,] 2) device array — a single fetch per
        dispatch."""
        from tpu_ddp.resilience.watchdog import touch_heartbeat
        cfg = self.config
        K = cfg.steps_per_dispatch
        multi = self.build_multi_step(K)
        n_slots = (self.mesh.shape[DATA_AXIS] if self.mesh is not None
                   else 1)
        local_slots = max(n_slots // max(jax.process_count(), 1), 1)
        depth_groups = cfg.dispatch_depth // K
        pipe = DispatchPipeline(depth_groups)

        def beat(step):
            if heartbeat is not None:
                touch_heartbeat(heartbeat[0], heartbeat[1], step)

        def harvest_single(harv_it, harv_step, fused):
            with span("tpu_ddp.train.harvest", it=harv_it):
                local, skipped = self._materialize_fused(fused)
                window.account(harv_it, local, harv_step)
                if self.guard is not None:
                    self.guard.record(harv_step, skipped, local)
                beat(harv_step)

        def materialize_group(fused):
            """(K, 2) host rows of [loss, skip] — this process's first
            replica under a mesh ((k, local_dp, 2) local shard)."""
            if self.mesh is not None:
                return np.asarray(
                    fused.addressable_shards[0].data)[:, 0, :]
            return np.asarray(fused)

        def harvest_group(first_it, last_step, fused):
            with span("tpu_ddp.train.harvest", it=first_it):
                rows = materialize_group(fused)
                for j in range(K):
                    # The group's state advanced by K; attribute each
                    # iteration its own global step.
                    window.account(first_it + j, float(rows[j, 0]),
                                   last_step - K + j + 1)
                    if self.guard is not None:
                        self.guard.record(last_step - K + j + 1,
                                          bool(rows[j, 1] > 0),
                                          float(rows[j, 0]))
                beat(last_step)

        it = start_iter
        buf: list = []

        def flush_singles():
            nonlocal state, it
            for bx, by in buf:
                sync_iter = depth_groups == 0 or it <= timer.last_iter
                timer.start()
                with span("tpu_ddp.train.put_batch"):
                    batch = self.put_batch(bx, by)
                with span("tpu_ddp.train.dispatch", it=it,
                          step=state.step):
                    state, fused = self.train_step_async(state, *batch)
                if sync_iter:
                    jax.block_until_ready(fused)
                timer.stop(it)
                pipe.submit(
                    fused,
                    lambda f, i=it, s=state.step: harvest_single(
                        i, s, f),
                    sync=sync_iter)
                it += 1
            buf.clear()

        for item in spanned(batches, "tpu_ddp.train.data_next"):
            if cfg.max_iters is not None \
                    and it + len(buf) >= cfg.max_iters:
                break
            buf.append(item)
            if len(buf) < K:
                continue
            shapes = {np.shape(b[0]) for b in buf}
            if len(shapes) == 1 and len(buf[0][1]) % local_slots == 0:
                # A group containing pre-window iterations holds the
                # compile; spreading it over its K iterations would leak
                # warm-up into the window the reference's protocol
                # excludes (iteration 0 discarded, part1/main.py:86-91).
                # Groups inside the timing window stay synchronous, as
                # in the streaming loop.
                timed = it >= timer.first_iter
                sync_group = depth_groups == 0 or it <= timer.last_iter
                if timed:
                    timer.start()
                with span("tpu_ddp.train.put_batch"):
                    xs = np.stack([b[0] for b in buf])
                    ys = np.stack([b[1] for b in buf])
                    group = self.put_batches(xs, ys)
                with span("tpu_ddp.train.dispatch", it=it,
                          step=state.step):
                    state, _ = multi(state, *group)
                fused = self._last_fused
                if sync_group:
                    jax.block_until_ready(fused)
                if timed:
                    timer.stop_many(it, K)
                pipe.submit(
                    fused,
                    lambda f, i=it, s=state.step: harvest_group(
                        i, s, f),
                    sync=sync_group)
                it += K
                buf.clear()
            else:
                flush_singles()  # non-uniform group: step them singly
        flush_singles()  # tail shorter than K
        pipe.drain()
        return state, window.epoch_stats(pipeline=pipe.stats())

    # ---- eval (reference test_model, part1/main.py:96-111) -------------

    def _build_eval_step(self):
        @program(DDP_EVAL_STEP)
        def step(params, images, labels):
            logits = self.model.apply(params,
                                      self._maybe_normalize(images))
            # Batch-mean loss (summed over batches by the caller, divided
            # by number of batches — the reference's per-batch averaging
            # semantics, part1/main.py:108) + top-1 correct count.
            return (cross_entropy_loss(logits, labels),
                    top1_correct(logits, labels))

        return jax.jit(step)

    def _build_sharded_eval(self):
        """Test batch sharded over dp, per-shard sums psum'd — N x less
        eval compute per device than the reference's every-node-evaluates-
        everything semantics (part2/part2b/main.py:89-93), with metrics
        identical to the replicated pass (weighted sums reduce to the
        same totals regardless of the split; wrap-padding rows carry
        weight 0). Opt-in via ``evaluate(..., sharded=True)``."""
        @program(DDP_EVAL_STEP)
        def body(params, images, labels, weights):
            logits = self.model.apply(params, self._maybe_normalize(images))
            per_ex = softmax_cross_entropy(logits, labels)
            loss_sum = lax.psum(jnp.sum(weights * per_ex), DATA_AXIS)
            correct = lax.psum(
                jnp.sum(weights * (jnp.argmax(logits, axis=-1) == labels)),
                DATA_AXIS)
            # Global valid-example count: the denominator when loader
            # weights mark sampler wrap-padding (process-sharded eval).
            wsum = lax.psum(jnp.sum(weights), DATA_AXIS)
            return (loss_sum.reshape(1), correct.reshape(1),
                    wsum.reshape(1))

        # Params arrive REPLICATED (evaluate() materializes FSDP's flat
        # shards first), so one body serves every strategy.
        return jax.jit(jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            check_vma=False))

    def _materialize_params(self, params):
        """FSDP: reassemble the flat dp shards into full replicated
        leaves for evaluation (XLA inserts the gather); identity for all
        other strategies."""
        if not self.is_fsdp:
            return params
        fn = getattr(self, "_materialize_fn", None)
        if fn is None:
            meta = self.zero3.meta
            fn = jax.jit(
                lambda t: jax.tree.map(
                    lambda x, m: x[:m.size].reshape(m.shape), t, meta),
                out_shardings=self._repl_sharding)
            self._materialize_fn = fn
        return fn(params)

    def evaluate(
        self,
        state: TrainState,
        batches,
        log: Callable[[str], None] = print,
        sharded: bool = False,
    ) -> dict:
        """Full test-set pass. By default, like the reference, the test
        set is NOT sharded — every node evaluates the full set redundantly
        (part2/part2b/main.py:89-93; SURVEY.md §3.4). ``sharded=True``
        (mesh required) splits each test batch over dp with psum'd
        loss/correct sums — 1/N the per-device compute, metrics identical
        for per-example models (tested in tests/test_engine.py). Caveat:
        batch-statistics BatchNorm (the VGG family's reference-faithful
        semantic, part1/model.py:24) computes its statistics over the
        SHARD under sharded eval, so its metrics shift slightly — the
        same per-replica-stats property the reference's report accepts
        for distributed training (report §3.2)."""
        total_loss = 0.0
        correct = 0
        seen = 0
        n_batches = 0
        use_sharded = sharded and self.mesh is not None
        if use_sharded and not hasattr(self, "_sharded_eval"):
            self._sharded_eval = self._build_sharded_eval()
        eval_params = self._materialize_params(state.params)

        def first_local(x):
            # Outputs are dp-sharded global arrays whose shards all
            # hold the same psum'd value; read the LOCAL shard (a
            # whole-array np.asarray is impossible in multi-process,
            # where some shards live on other processes).
            return float(np.ravel(x.addressable_shards[0].data)[0])

        # Deferred materialization (round 6, same discipline as the
        # train pipeline): with dispatch_depth > 0 the per-batch scalar
        # fetches are queued and resolved behind a bounded window, so
        # eval batches dispatch back-to-back instead of paying one host
        # round-trip each. The accumulated metrics are identical — only
        # when the fetch happens moves. dispatch_depth=0 keeps the
        # synchronous per-batch reads.
        lazy = self.config.dispatch_depth > 0
        pending: list = []
        max_pending = max(8, 4 * self.config.dispatch_depth)

        def resolve(rec):
            nonlocal total_loss, correct, seen, n_batches
            if rec[0] == "sharded":
                _, loss_sum, corr_h, wsum = rec
                n = first_local(wsum)
                total_loss += first_local(loss_sum) / max(n, 1.0)
                correct += int(round(first_local(corr_h)))
                seen += int(round(n))
            else:
                _, loss_h, corr_h, n = rec
                total_loss += float(loss_h)
                correct += int(corr_h)
                seen += n
            n_batches += 1

        def push(rec):
            if not lazy:
                resolve(rec)
                return
            pending.append(rec)
            if len(pending) > max_pending:
                resolve(pending.pop(0))

        for batch in batches:
            images, labels = batch[0], batch[1]
            batch_w = batch[2] if len(batch) > 2 else None
            if batch_w is not None and not use_sharded:
                # A process-sharded loader's weight column marks the
                # sampler's wrap-padding duplicates; a replicated eval
                # must not count them as real examples — drop them
                # host-side so the metrics stay per-shard-exact rather
                # than silently inflated.
                keep = np.asarray(batch_w) > 0
                images, labels = images[keep], labels[keep]
                batch_w = None
                if len(labels) == 0:
                    continue
            if use_sharded:
                if batch_w is None and jax.process_count() > 1:
                    # The plain eval loader feeds EVERY process the full
                    # test set (reference part2/part2b/main.py:89-93);
                    # sharding that would psum each example P times.
                    # A process-sharded loader announces itself by
                    # yielding (images, labels, weights) triples —
                    # create_data_loaders(shard_eval=True).
                    raise ValueError(
                        "evaluate(sharded=True) in a multi-process run "
                        "needs a process-sharded eval loader (weights "
                        "triples): create_data_loaders(shard_eval=True)"
                        ". The default replicated loader would be "
                        "double-counted by the dp-psum.")
                xb, yb, wb = self.put_batch(images, labels, batch_w)
                loss_sum, corr, wsum = self._sharded_eval(eval_params,
                                                          xb, yb, wb)
                push(("sharded", loss_sum, corr, wsum))
                continue
            if self.mesh is not None:
                images = jax.device_put(images, self._repl_sharding)
                labels = jax.device_put(labels, self._repl_sharding)
            else:
                images, labels = jnp.asarray(images), jnp.asarray(labels)
            loss, corr = self._eval_step(eval_params, images, labels)
            push(("repl", loss, corr, int(labels.shape[0])))
        for rec in pending:
            resolve(rec)
        avg_loss = total_loss / max(n_batches, 1)
        accuracy = correct / max(seen, 1)
        log(f"Test set: average loss {avg_loss:.4f}, "
            f"accuracy {correct}/{seen} ({100.0 * accuracy:.2f}%)")
        self.metrics.log("eval", test_loss=round(avg_loss, 5),
                         test_accuracy=round(accuracy, 5), seen=seen)
        return {"test_loss": avg_loss, "test_accuracy": accuracy,
                "correct": correct, "seen": seen}
