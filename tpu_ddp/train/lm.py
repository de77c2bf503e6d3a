"""Language-model training engine: data x sequence x tensor parallel.

No reference counterpart (the reference trains VGG on CIFAR with DP only,
SURVEY.md §2/§5) — this engine exists because long-context and model-
sharded training are first-class here. One jitted ``shard_map`` step over
a (dp, sp, mp) mesh:

- token/target batches (B, L) are sharded batch-over-``dp`` AND
  sequence-over-``sp`` (replicated over ``mp``);
- attention inside the model runs sequence-parallel over ``sp`` — ring
  K/V rotation (tpu_ddp/parallel/ring_attention.py, the default) or
  Ulysses all-to-all head re-sharding (tpu_ddp/parallel/ulysses.py,
  ``sp_mode="ulysses"``) — so the residual stream only ever holds its
  L/sp chunk;
- block parameters shard over ``mp`` per the model's ``param_specs()``
  (Megatron column/row layout, tpu_ddp/parallel/tensor_parallel.py);
  LayerNorms/embeddings/head and the optimizer moments of every leaf live
  in the SAME sharding as the leaf;
- the loss is the global per-token mean: local weighted sums are
  ``psum``'d over (dp, sp) — the ``mp`` shards compute it redundantly;
- gradients are ``pmean``'d over (dp, sp): tp-sharded leaves sync their
  own slice, replicated leaves are already identical across ``mp`` by the
  tensor-parallel backward construction.

Next-token shift happens on host (``make_lm_batch``): inputs = tokens[:-1],
targets = tokens[1:], so no cross-chunk halo exchange is needed.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_ddp.ops.loss import (chunked_vocab_cross_entropy,
                              softmax_cross_entropy)
from tpu_ddp.ops.optim import AdamW
from tpu_ddp.parallel.mesh import (DATA_AXIS, EXPERT_AXIS, MODEL_AXIS,
                                   PIPE_AXIS, SEQ_AXIS)
from tpu_ddp.utils.profiling import (LM_TRAIN_MULTI_STEP, LM_TRAIN_STEP,
                                     program, span)


def _spec_axes(spec) -> set:
    """Mesh axis names a PartitionSpec shards over."""
    names = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            names.update(entry)
        else:
            names.add(entry)
    return names


@dataclasses.dataclass
class LMTrainState:
    params: Any
    opt_state: Any
    step: int = 0


def make_lm_batch(tokens: np.ndarray):
    """(B, L+1) token ids -> (inputs, targets), each (B, L)."""
    tokens = np.asarray(tokens)
    return tokens[:, :-1], tokens[:, 1:]


def format_route_stats(stats) -> str:
    """One metrics-line fragment from :meth:`LMTrainer.route_stats`
    output — ``" moe dropped=2.1%/0.0% imbalance=1.31/1.05"``, one slot
    per routed layer — so training loops and bench probes print the
    routing-health counters the same way. Empty string for dense models
    (empty stats), so call sites append it unconditionally."""
    if not stats:
        return ""
    drop = "/".join(f"{float(s['dropped_frac']) * 100:.1f}%"
                    for s in stats)
    imb = "/".join(f"{float(s['imbalance']):.2f}" for s in stats)
    return f" moe dropped={drop} imbalance={imb}"


def _is_spec(x):
    return isinstance(x, P)


class _MeshTrainer:
    """Shared wiring for shard_map'd LM trainers: sharding trees from
    spec trees, train-step compilation, and the step loop. Subclasses set
    ``mesh``/``optimizer``/``_param_specs``/``_opt_specs`` and implement
    ``_base_step`` (the per-shard step body)."""

    def _shardings(self, specs):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=_is_spec)

    def _extra_in_specs(self) -> tuple:
        """Specs for trainer-specific trailing _base_step args (e.g. the
        LMTrainer's per-step dropout key, replicated)."""
        return ()

    def _extra_args(self, state) -> tuple:
        """Values for those trailing args, built per call."""
        return ()

    def _compile_step(self, batch_spec, loss_spec):
        @program(LM_TRAIN_STEP)
        def step(*args):
            return self._base_step(*args)

        mapped = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(self._param_specs, self._opt_specs, batch_spec,
                      batch_spec, *self._extra_in_specs()),
            out_specs=(self._param_specs, self._opt_specs, loss_spec),
            check_vma=False,
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def _place_state(self, params, opt_state) -> LMTrainState:
        params = jax.device_put(params, self._param_shardings)
        opt_state = jax.device_put(opt_state, self._opt_shardings)
        return LMTrainState(params=params, opt_state=opt_state)

    def _decay_mask(self, params):
        """The optimizer's decay policy on ITS view of the leaves;
        overridden where the trainer re-lays-out parameters."""
        return self.optimizer.decay_mask(params)

    def put_batch(self, inputs, targets):
        """Host (B, L) inputs / targets -> device arrays in the batch
        sharding; the subclass's ``_put_batch`` checks divisibility."""
        with span("tpu_ddp.lm.put_batch", tokens=np.size(inputs)):
            return self._put_batch(inputs, targets)

    _last_loss = None   # the loss of the step before, on the device

    def train_step(self, state: LMTrainState, inputs, targets):
        # dry: the step before was already done (is_ready, which does
        # not wait), so the device had run out of work
        dry = {} if self._last_loss is None \
            else {"dry": int(self._last_loss.is_ready())}
        with span("tpu_ddp.lm.train_step", step=state.step, **dry):
            params, opt_state, loss = self._train_step(
                state.params, state.opt_state, inputs, targets,
                *self._extra_args(state))
        self._last_loss = loss
        return LMTrainState(params, opt_state, state.step + 1), loss

    def lower_train_step(self, state: LMTrainState, inputs, targets):
        """Lower (never run) the jitted train step — the graph_audit
        surface (scripts/graph_audit.py): what the lockstep auditor
        fingerprints is exactly the program ``train_step`` dispatches,
        collective order included (the MoE step's two all_to_alls are
        the divergent-order deadlock class it hunts)."""
        return self._train_step.lower(
            state.params, state.opt_state, inputs, targets,
            *self._extra_args(state))

    def _clip_by_global_norm(self, grads, specs):
        """Scale ``grads`` so their GLOBAL L2 norm is <= clip_grad_norm
        (torch.nn.utils.clip_grad_norm_ semantics, computed cross-layout).

        Call on SYNCED gradients. Each leaf's squared sum is psum'd over
        exactly the mesh axes that shard it per its spec — distinct
        shards hold distinct elements; axes a leaf is replicated over
        must NOT be summed (they would multi-count it). Every device
        lands on the same norm, so the scale is consistent everywhere.
        One psum per distinct axis set, not per leaf."""
        with jax.named_scope("clip"):
            g_l, treedef = jax.tree.flatten(grads)
            s_l = jax.tree.leaves(specs, is_leaf=_is_spec)
            groups: dict = {}
            for g, spec in zip(g_l, s_l):
                axes = tuple(sorted(a for a in _spec_axes(spec)
                                    if self.mesh.shape[a] > 1))
                groups.setdefault(axes, []).append(
                    jnp.sum(jnp.square(g.astype(jnp.float32))))
            sq = jnp.float32(0.0)
            for axes, sums in groups.items():
                s = sum(sums)
                if axes:
                    s = lax.psum(s, axes)
                sq = sq + s
            from tpu_ddp.ops.optim import clip_scale_from_sq, clip_tree
            return clip_tree(treedef.unflatten(g_l),
                             clip_scale_from_sq(sq, self.clip_grad_norm))

    def _put_sharded(self, array, sharding):
        from tpu_ddp.parallel.mesh import put_sharded
        return put_sharded(array, sharding)

    @staticmethod
    def _global_batch(local_b: int, shard_ways: int | None = None) -> int:
        """Divisibility constraints apply to the ASSEMBLED batch: in a
        multi-process launch each process's put_batch sees only its own
        shard of the batch axis. ``shard_ways`` = how many ways the
        batch axis is sharded (dp*ep for both trainers — pipeline
        tokens are data-parallel over dp x ep too since round 5):
        processes in the same model-parallel group feed the
        SAME rows, so the multiplier is capped at the shard count —
        ``local_b * process_count`` alone would overcount by the tp/pp
        replication factor and false-pass the divisibility checks."""
        p = jax.process_count()
        return local_b * (min(p, shard_ways) if shard_ways else p)

    def sharding_plan(self):
        """The serializable layout contract of this trainer — per-tree
        PartitionSpecs plus the mesh axis sizes they were built against
        (tpu_ddp/parallel/redistribute.py). The strategy string encodes
        the layout-changing switches (fsdp/zero) so two trainers whose
        flat layouts differ can never be declared compatible by spec
        coincidence."""
        from tpu_ddp.parallel.redistribute import ShardingPlan
        strategy = type(self).__name__.lower()
        if getattr(self, "is_fsdp", False):
            strategy += "+fsdp"
        if getattr(self, "opt_zero2", False):
            strategy += "+zero2"
        elif getattr(self, "opt_zero1", False):
            strategy += "+zero1"
        return ShardingPlan(
            strategy=strategy,
            mesh_axes=tuple((str(n), int(s))
                            for n, s in self.mesh.shape.items()),
            param_specs=self._param_specs,
            opt_specs=self._opt_specs,
            comp_specs=None,
            batch_spec=P((DATA_AXIS, EXPERT_AXIS), SEQ_AXIS),
            stage_layout=getattr(self, "_stage_layout", None))

    # ---- checkpoint / resume (no reference equivalent, SURVEY.md §5) ---

    def save_checkpoint(self, directory: str, state: LMTrainState,
                        keep_last: int | None = None,
                        background: bool = False) -> str | None:
        """Gather leaves to host LEAF BY LEAF (each gather is a collective
        all processes must enter), then process 0 writes. Per-leaf keeps
        the transient device-memory peak at one leaf's replicated size —
        a whole-tree replication would materialize the full params +
        optimizer state on every device at once, OOMing exactly the
        tp/pp/ZeRO-sharded models that needed sharding to fit."""
        gathered = self._gather_to_host((state.params, state.opt_state))
        if jax.process_index() != 0:
            return None
        from tpu_ddp.utils import checkpoint as ckpt
        params, opt_state = gathered
        if getattr(self, "is_fsdp", False):
            # Checkpoints hold CANONICAL shapes, never the flat dp-padded
            # layout — so they restore at any dp size or as replicated.
            params = self.zero3.unshard_host(params)
            opt_state = self.zero3.canonicalize_opt_host(opt_state)
        elif getattr(self, "opt_zero1", False):
            opt_state = self.optimizer.canonicalize_opt_host(opt_state)
        params, opt_state = self._to_canonical_host(params, opt_state)
        tree = {"params": params, "opt_state": opt_state,
                "step": np.int64(state.step)}
        # The layout contract rides next to the steps: a restore onto a
        # different world can check compatibility before touching bytes.
        self.sharding_plan().save(directory)
        if background:
            # Gathers above already ran synchronously (collectives);
            # only serialization + I/O move off-thread.
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
                           step: int | None = None) -> LMTrainState:
        """Load a checkpoint (latest by default) and re-place every leaf
        in its spec's sharding, as :meth:`init_state` does. FSDP
        re-flattens the canonical on-disk shapes for THIS trainer's dp."""
        from tpu_ddp.utils import checkpoint as ckpt
        if getattr(self, "is_fsdp", False):
            params_t = self._params_template
            opt_t = jax.eval_shape(self.zero3.inner.init, params_t)
            shapes = {"params": params_t, "opt_state": opt_t}
        elif getattr(self, "opt_zero1", False):
            params_t = self._params_template  # built with the wrapper
            # Per-cell factored layouts (FactoredZeRO1 with partitions)
            # have their OWN canonical form — ask the wrapper; flat
            # ZeRO1's canonical form is the inner optimizer's shapes.
            if hasattr(self.optimizer, "canonical_opt_template"):
                opt_t = self.optimizer.canonical_opt_template(params_t)
            else:
                opt_t = jax.eval_shape(self.optimizer.inner.init,
                                       params_t)
            shapes = {"params": params_t, "opt_state": opt_t}
        else:
            shapes = jax.eval_shape(
                lambda: (lambda s: {"params": s.params,
                                    "opt_state": s.opt_state})(
                    self.init_state()))
        template = {**shapes, "step": np.int64(0)}
        restored, _ = ckpt.restore_checkpoint(directory, template, step)
        params, opt_state = restored["params"], restored["opt_state"]
        params, opt_state = self._from_canonical_host(params, opt_state)
        if getattr(self, "is_fsdp", False):
            params = self.zero3.shard_params(params)
            opt_state = self.zero3.flatten_opt(opt_state)
        elif getattr(self, "opt_zero1", False):
            opt_state = self.optimizer.flatten_opt(opt_state)
        placed = self._place_state(params, opt_state)
        return LMTrainState(params=placed.params,
                            opt_state=placed.opt_state,
                            step=int(restored["step"]))

    def _gather_to_host(self, tree):
        from tpu_ddp.utils.checkpoint import gather_tree_to_host
        return gather_tree_to_host(tree, NamedSharding(self.mesh, P()))

    def params_to_host(self, state):
        """Canonical host numpy params only — the snapshot surface the
        weight-streaming publisher (tpu_ddp/publish/) feeds. Mirrors
        the params half of :meth:`save_checkpoint`: FSDP unshards to
        canonical shapes, interleaved pipelines unpermute to dense
        layer order, so any training layout publishes the same tree."""
        params = self._gather_to_host(state.params)
        if getattr(self, "is_fsdp", False):
            params = self.zero3.unshard_host(params)
        if hasattr(self, "canonical_params"):
            params = self.canonical_params(params)
        return jax.tree.map(np.asarray, params)

    def attach_publisher(self, publisher) -> None:
        """Scenario loops (tpu_ddp/publish/rollout.py) drive
        ``publisher.after_step`` directly; this mirror of the engine
        Trainer hook exists so either trainer slots into launch
        plumbing unchanged."""
        self._publisher = publisher

    def _to_canonical_host(self, params, opt_state):
        """Trainer layout -> canonical on-disk layout (identity here;
        the interleaved pipeline unpermutes its stacked layer rows)."""
        return params, opt_state

    def _from_canonical_host(self, params, opt_state):
        """Inverse of :meth:`_to_canonical_host` at restore time."""
        return params, opt_state

    # ---- K-step scan (engine.py's multi-step contract, LM rung) -------

    def build_multi_step(self, k: int):
        """One jitted program scanning ``k`` train steps: batches arrive
        stacked on a leading ``k`` axis, losses come back stacked, and
        the host dispatches once per ``k`` steps — the engine.Trainer
        ``build_multi_step`` contract on the LM/pipeline rung. Per-step
        extras (the dropout key) are folded host-side for each scanned
        step from ``state.step``, so a K-step program advances the key
        sequence exactly as ``k`` single steps do (resume-exact).
        Compiled programs are memoized per ``k``."""
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        cache = getattr(self, "_multi_step_cache", None)
        if cache is None:
            cache = self._multi_step_cache = {}
        if k not in cache:
            batch_spec = P((DATA_AXIS, EXPERT_AXIS), SEQ_AXIS)
            extra_specs = self._extra_in_specs()

            @program(LM_TRAIN_MULTI_STEP)
            def body(params, opt_state, inputs_k, targets_k, *extras_k):
                def step(carry, xs):
                    p, o = carry
                    p, o, mean = self._base_step(p, o, xs[0], xs[1],
                                                 *xs[2:])
                    return (p, o), mean
                (p, o), means = lax.scan(
                    step, (params, opt_state),
                    (inputs_k, targets_k, *extras_k))
                return p, o, means

            mapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(self._param_specs, self._opt_specs,
                          P(None, *tuple(batch_spec)),
                          P(None, *tuple(batch_spec)),
                          *tuple(P(None, *tuple(s))
                                 for s in extra_specs)),
                out_specs=(self._param_specs, self._opt_specs,
                           P(None, *tuple(batch_spec))),
                check_vma=False,
            )
            cache[k] = jax.jit(mapped, donate_argnums=(0, 1))

        stepped = cache[k]

        def run(state: LMTrainState, inputs_k, targets_k):
            rows = [self._extra_args(
                dataclasses.replace(state, step=state.step + i))
                for i in range(k)]
            extras = (tuple(jnp.stack(col) for col in zip(*rows))
                      if rows and rows[0] else ())
            params, opt_state, losses = stepped(
                state.params, state.opt_state, inputs_k, targets_k,
                *extras)
            return (LMTrainState(params, opt_state, state.step + k),
                    losses)

        return run


class LMTrainer(_MeshTrainer):
    """Wires a TransformerLM + AdamW into a dp x sp x tp x ep sharded
    step. Token batches are data-parallel over BOTH ``dp`` and ``ep``
    (expert weights shard over ``ep``; tokens reach their expert's device
    via the MoE layer's all_to_all, tpu_ddp/parallel/moe.py)."""

    def __init__(self, model, mesh: Mesh, optimizer: AdamW | None = None,
                 moe_aux_coef: float = 0.01,
                 param_sharding: str = "replicated",
                 opt_sharding: str = "replicated",
                 vocab_chunk: int = 0, sp_mode: str = "ring",
                 grad_accum: int = 1, dropout_seed: int = 0,
                 clip_grad_norm: float | None = None):
        self.mesh = mesh
        self.dp = mesh.shape[DATA_AXIS]
        self.sp = mesh.shape[SEQ_AXIS]
        self.tp = mesh.shape.get(MODEL_AXIS, 1)
        self.ep = mesh.shape.get(EXPERT_AXIS, 1)
        self.moe_aux_coef = moe_aux_coef
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        # Validate even on sp=1 meshes (where the mode is inert), so a
        # typo'd config fails at first use, not after scaling sp up.
        if sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sequence-parallel mode {sp_mode!r};"
                             " expected 'ring' or 'ulysses'")
        # > 1: each step scans this many microbatches, accumulating f32
        # gradients before the (single) sync + optimizer update.
        self.grad_accum = grad_accum
        # Per-step dropout keys derive from this seed + the state's step
        # (resume-exact); the key is inert when model.dropout_rate == 0.
        self._dropout_key = jax.random.key(dropout_seed)
        # > 0: compute the loss via chunked-vocab CE, never materializing
        # the (T, V) logits (tpu_ddp/ops/loss.py) — the train step's
        # largest buffer at long context. Value = vocab slice width.
        self.vocab_chunk = vocab_chunk
        if vocab_chunk and model.vocab_size % vocab_chunk:
            raise ValueError(f"vocab_size={model.vocab_size} not "
                             f"divisible by vocab_chunk={vocab_chunk}")
        if param_sharding not in ("replicated", "fsdp"):
            raise ValueError(f"unknown param_sharding {param_sharding!r}; "
                             "choose 'replicated' or 'fsdp'")
        self.is_fsdp = param_sharding == "fsdp"
        if self.sp > 1:
            # "ring" rotates K/V over sp; "ulysses" re-shards heads<->
            # sequence with two all_to_alls (tpu_ddp/parallel/ulysses.py).
            model = model.with_sequence_parallel(SEQ_AXIS, self.sp,
                                                 mode=sp_mode)
        if self.tp > 1:
            model = model.with_tensor_parallel(MODEL_AXIS, self.tp)
        if self.ep > 1:
            model = model.with_expert_parallel(EXPERT_AXIS, self.ep)
        self.model = model
        # All axes the batch (and therefore the loss) is sharded over.
        self._data_axes = (DATA_AXIS, SEQ_AXIS, EXPERT_AXIS)
        self.optimizer = optimizer or AdamW()
        # Global-norm gradient clipping (round-3 verdict item 6):
        # torch.nn.utils.clip_grad_norm_ semantics, with the norm
        # computed across whatever layout the gradients live in
        # (replicated, tp/ep-sharded, dp-scattered ZeRO slices, flat
        # FSDP shards, pp stages) — see _clip_by_global_norm and
        # ZeRO1.apply_scattered.
        if clip_grad_norm is not None and clip_grad_norm <= 0:
            raise ValueError(f"clip_grad_norm must be > 0, got "
                             f"{clip_grad_norm}")
        self.clip_grad_norm = clip_grad_norm
        # ZeRO-1: optimizer state sharded 1/dp, reduce_scatter+all_gather
        # in place of the gradient all-reduce (tpu_ddp/parallel/zero.py).
        # Adafactor gets the row-sharded FactoredZeRO1 (its factored
        # moments cannot ride ZeRO1's flat slices); elementwise
        # optimizers (AdamW/SGD) the flat ZeRO1.
        # ZeRO-2 (round-3 verdict item 5) = ZeRO-1 state layout PLUS
        # dp-scattered gradient accumulation: each microbatch's grads
        # are reduce-scattered immediately and the f32 accumulation
        # buffer holds 1/dp slices — accumulation memory drops ~dp x.
        if opt_sharding not in ("replicated", "zero1", "zero2"):
            raise ValueError(f"unknown opt_sharding {opt_sharding!r}; "
                             "choose 'replicated', 'zero1' or 'zero2'")
        self.opt_zero1 = opt_sharding in ("zero1", "zero2")
        self.opt_zero2 = opt_sharding == "zero2"
        if self.opt_zero1:
            if self.is_fsdp:
                raise ValueError(
                    "opt_sharding='zero1' is redundant under "
                    "param_sharding='fsdp' (ZeRO-3 already shards the "
                    "optimizer state)")
            from tpu_ddp.ops.optim import Adafactor
            from tpu_ddp.parallel.zero import FactoredZeRO1, ZeRO1
            self._params_template = jax.eval_shape(
                lambda: self.model.init(jax.random.key(0)))
            # Explicit type dispatch: Adafactor's factored state needs
            # the row-sharded wrapper; everything elementwise (AdamW,
            # SGD) takes the flat one. An unknown factored optimizer
            # fails loudly in ZeRO1's map_param_like rather than being
            # silently re-laid-out wrong.
            if isinstance(self.optimizer, Adafactor):
                if self.opt_zero2:
                    raise ValueError(
                        "opt_sharding='zero2' (dp-scattered flat "
                        "gradient accumulation) does not compose with "
                        "Adafactor's row-sharded factored state; use "
                        "'zero1' or an elementwise optimizer")
                if self.clip_grad_norm is not None:
                    raise ValueError(
                        "clip_grad_norm with opt_sharding='zero1' "
                        "Adafactor is not supported (Adafactor already "
                        "clips by update RMS, ops/optim.py); use AdamW/"
                        "SGD or drop the clip")
                # Round-5: tp/ep-sharded leaves compose via PER-CELL
                # factoring — row geometry from each cell's LOCAL slice,
                # dp row-sharding within the cell (zero.py docstrings).
                self.optimizer = FactoredZeRO1(
                    self.optimizer, DATA_AXIS, self.dp,
                    template=self._params_template,
                    param_specs=self.model.param_specs(),
                    mesh_axis_sizes=dict(mesh.shape))
            else:
                # Elementwise optimizers compose with tp/ep: each
                # mp/ep-sharded leaf's state is laid out per model-
                # parallel cell and dp-sharded within it
                # (tpu_ddp/parallel/zero.py ZeRO1 docstring).
                self.optimizer = ZeRO1(
                    self.optimizer, DATA_AXIS, self.dp,
                    template=self._params_template,
                    param_specs=self.model.param_specs(),
                    mesh_axis_sizes=dict(mesh.shape))
        if self.is_fsdp:
            from tpu_ddp.parallel.zero import ZeRO3
            self._params_template = jax.eval_shape(
                lambda: self.model.init(jax.random.key(0)))
            # Partition-aware flat layout (round-3 verdict item 3):
            # tp/ep-sharded leaves lay out per model-parallel cell,
            # dp-sharded within it (P((mp..., dp))); gather_params
            # reassembles each cell's LOCAL slice, which is exactly the
            # leaf the tensor-parallel model code expects in shard_map.
            self._orig_specs = self.model.param_specs()
            self.zero3 = ZeRO3(self.optimizer, DATA_AXIS, self.dp,
                               template=self._params_template,
                               param_specs=self._orig_specs,
                               mesh_axis_sizes=dict(mesh.shape))
            self._param_specs = self.zero3.flat_param_specs()
            self._opt_specs = self.zero3.state_specs()
        else:
            self._param_specs = self.model.param_specs()
            from tpu_ddp.ops.optim import Adafactor
            if (isinstance(self.optimizer, Adafactor)
                    and (self.tp > 1 or self.ep > 1)):
                # Round-5: replicated-opt Adafactor under tp/ep — wrap
                # into the per-cell layout (each mp/ep cell factors its
                # own slice; state replicated over dp).
                from tpu_ddp.parallel.zero import CellAdafactor
                self.optimizer = CellAdafactor(
                    self.optimizer,
                    template=jax.eval_shape(
                        lambda: self.model.init(jax.random.key(0))),
                    param_specs=self._param_specs,
                    mesh_axis_sizes=dict(mesh.shape))
            self._opt_specs = self.optimizer.state_specs(self._param_specs)
        batch_spec = P((DATA_AXIS, EXPERT_AXIS), SEQ_AXIS)
        self._batch_sharding = NamedSharding(mesh, batch_spec)
        self._param_shardings = self._shardings(self._param_specs)
        self._opt_shardings = self._shardings(self._opt_specs)
        self._train_step = self._compile_step(batch_spec, batch_spec)

    def init_state(self, seed: int = 0) -> LMTrainState:
        """Init GLOBAL params from the seed, then place every leaf in its
        spec's sharding (tp leaves split over ``mp``, rest replicated;
        under fsdp every leaf is flattened into dp shards)."""
        params = self.model.init(jax.random.key(seed))
        if self.is_fsdp:
            params = self.zero3.shard_params(params)
            return self._place_state(params, self.zero3.init(params))
        return self._place_state(params, self.optimizer.init(params))

    def _sync_grads(self, grads, skip_axes=()):
        """Mean over the data axes, per leaf. A leaf sharded over ``ep``
        (stacked expert weights) owns its slice, so no ep-collective —
        BUT its gradient already holds the SUM over every token shard's
        contribution (the backward all_to_all delivered them), so the
        mean over those excluded axes becomes a plain division.
        mp-replicated leaves are already identical across mp by the
        tensor-parallel backward construction (tp_input).

        ``skip_axes``: data axes some OTHER mechanism synchronizes —
        ZeRO-1 passes ``(DATA_AXIS,)`` because its psum_scatter IS the
        dp half of the sync — kept out of the pmean here, one algebra
        for every optimizer layout."""
        def leaf(g, spec):
            sharded = _spec_axes(spec)
            sync = tuple(a for a in self._data_axes
                         if a not in sharded and a not in skip_axes)
            if sync:
                g = lax.pmean(g, sync)
            excluded = int(np.prod([self.mesh.shape[a]
                                    for a in self._data_axes
                                    if a in sharded]))
            return g / excluded if excluded > 1 else g
        return jax.tree.map(leaf, grads, self._param_specs)

    def _extra_in_specs(self) -> tuple:
        return (P(),)  # dropout key: replicated on every shard

    def _extra_args(self, state) -> tuple:
        # Folding by step happens HOST-side (step is a Python int), so
        # each step deterministically gets a fresh key and a restored
        # run continues the same key sequence.
        return (jax.random.fold_in(self._dropout_key, state.step),)

    def _decorrelate_rng(self, rng):
        """Distinct dropout keys per (dp, sp, ep) shard — those hold
        different tokens — but the SAME key across mp shards, whose
        replicated residual stream must see one mask."""
        if self.model.dropout_rate <= 0.0:
            return None
        for ax in self._data_axes:
            rng = jax.random.fold_in(rng, lax.axis_index(ax))
        return rng

    def _accumulate(self, grad_fn, params, inputs, targets, rng):
        """(local_mean_loss, grads) over ``grad_accum`` microbatches.

        A=1 is one plain forward/backward. A>1 splits the local batch
        into A microbatches and ``lax.scan``s forward+backward over them,
        summing gradients in f32 — peak activation memory drops by ~A
        while, for DENSE models, the optimizer sees exactly the
        full-batch gradient (microbatch shards are equal-sized, so
        mean-of-microbatch-means == the global token mean;
        tests/test_grad_accum.py). MoE models route per microbatch:
        expert capacity and the load-balance aux loss are computed from
        each microbatch's token mix, so the accumulated step is the mean
        of A smaller routing problems, not bit-equal to one big one —
        inherent to accumulation (routing is nonlinear in batch
        composition), and how every major MoE stack behaves. The standard
        big-batch lever when the per-step batch no longer fits HBM; no
        reference counterpart (its global batch of 256 CIFAR images needs
        no splitting, part2/part2b/main.py:177).
        """
        A = self.grad_accum
        # ZeRO-2: reduce-scatter each microbatch's gradients over dp
        # immediately and accumulate the f32 SLICES — the accumulation
        # buffer drops from O(P) to O(P/dp) per device, at the cost of
        # one scatter per microbatch instead of one per step (the
        # classic ZeRO-2 memory/comm trade, arXiv:1910.02054 §5).
        scatter = self.optimizer.scatter_grads if self.opt_zero2 else None
        if A == 1:
            (_, local_mean), grads = grad_fn(params, inputs, targets, rng)
            return local_mean, (scatter(grads) if scatter else grads)
        mb = inputs.shape[0] // A
        xs = (inputs.reshape(A, mb, inputs.shape[1]),
              targets.reshape(A, mb, targets.shape[1]),
              jnp.arange(A))

        def body(carry, xt):
            g_acc, l_acc = carry
            # Fresh dropout mask per microbatch (fold by index).
            r = jax.random.fold_in(rng, xt[2]) if rng is not None else None
            (_, lm), g = grad_fn(params, xt[0], xt[1], r)
            if scatter is not None:
                g = scatter(g)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), g_acc, g)
            return (g_acc, l_acc + lm), None

        if scatter is not None:
            g0 = self.optimizer.shard_zeros(params)
        else:
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
        (g_sum, l_sum), _ = lax.scan(body, (g0, jnp.float32(0.0)), xs)
        inv = 1.0 / float(A)
        return l_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    def _base_step(self, params, opt_state, inputs, targets, rng):
        rng = self._decorrelate_rng(rng)

        def loss_terms(p, inputs, targets, rng):
            if self.vocab_chunk:
                hidden, aux = self.model.trunk_with_aux(p, inputs,
                                                        rng=rng)
                with jax.named_scope("loss"):
                    nll = chunked_vocab_cross_entropy(
                        hidden.reshape(-1, hidden.shape[-1]), p["head"],
                        targets.reshape(-1), self.vocab_chunk)
            else:
                logits, aux = self.model.apply_with_aux(p, inputs,
                                                        rng=rng)
                with jax.named_scope("loss"):
                    nll = softmax_cross_entropy(
                        logits.reshape(-1, logits.shape[-1]),
                        targets.reshape(-1))
            with jax.named_scope("loss"):
                local_sum = jnp.sum(nll)
                local_n = jnp.float32(nll.size)
                total = lax.psum(local_n, self._data_axes)
                n_shards = lax.psum(1.0, self._data_axes)
                # Scale so pmean-of-grads == grad of the GLOBAL token
                # mean. mp shards hold the same tokens and compute the
                # same loss.
                loss_for_grad = (n_shards * local_sum / total
                                 + self.moe_aux_coef * aux)
                return loss_for_grad, local_sum / local_n

        if self.is_fsdp:
            def grad_fn(p, x, y, r):
                # all_gather over dp materializes full leaves transiently;
                # the AD transpose reduce-scatters cotangents, delivering
                # this worker's dp-SUMMED gradient shard directly.
                return jax.value_and_grad(
                    lambda flat: loss_terms(self.zero3.gather_params(flat),
                                            x, y, r), has_aux=True)(p)
        else:
            def grad_fn(p, x, y, r):
                return jax.value_and_grad(
                    lambda q: loss_terms(q, x, y, r), has_aux=True)(p)

        local_mean, grads = self._accumulate(grad_fn, params, inputs,
                                             targets, rng)

        if self.is_fsdp:
            # The dp SUM already happened (the all_gather transpose
            # reduce-scattered it); finish the sync per leaf with
            # _sync_grads' algebra on the flat dp shards: mean over the
            # non-dp data axes the ORIGINAL leaf is not sharded over,
            # then divide by dp and by any data-axis shard count (an
            # ep-sharded leaf's grad already holds its token-shard sum).
            def leaf(g, spec):
                sharded = _spec_axes(spec)
                sync = tuple(a for a in self._data_axes
                             if a not in sharded and a != DATA_AXIS)
                if sync:
                    g = lax.pmean(g, sync)
                excluded = int(np.prod([self.mesh.shape[a]
                                        for a in self._data_axes
                                        if a in sharded]))
                return g / float(self.dp * excluded)
            with jax.named_scope("grad_sync"):
                grads = jax.tree.map(leaf, grads, self._orig_specs)
            if self.clip_grad_norm is not None:
                # Flat dp shards: the flat specs carry the (mp..., dp)
                # axes each slice is distinct over.
                grads = self._clip_by_global_norm(grads,
                                                  self._param_specs)
            with jax.named_scope("optimizer"):
                params, opt_state = self.zero3.apply(params, grads,
                                                     opt_state)
            return params, opt_state, local_mean.reshape(1, 1)

        if self.opt_zero1:
            # Sync over the non-dp data axes here; the ZeRO wrapper's
            # psum_scatter performs the dp half (and computes its own
            # decay mask from the full local leaves). Under ZeRO-2 the
            # accumulation already scattered over dp — the same non-dp
            # algebra applies elementwise to the f32 slices (linear ops
            # commute with slicing).
            with jax.named_scope("grad_sync"):
                grads = self._sync_grads(grads, skip_axes=(DATA_AXIS,))
            with jax.named_scope("optimizer"):
                if self.opt_zero2:
                    params, opt_state = self.optimizer.apply_scattered(
                        params, grads, opt_state,
                        clip_norm=self.clip_grad_norm)
                elif self.clip_grad_norm is not None:
                    params, opt_state = self.optimizer.apply(
                        params, grads, opt_state,
                        clip_norm=self.clip_grad_norm)
                else:
                    params, opt_state = self.optimizer.apply(
                        params, grads, opt_state)
            return params, opt_state, local_mean.reshape(1, 1)

        with jax.named_scope("grad_sync"):
            grads = self._sync_grads(grads)
        if self.clip_grad_norm is not None:
            grads = self._clip_by_global_norm(grads, self._param_specs)
        with jax.named_scope("optimizer"):
            params, opt_state = self.optimizer.apply(
                params, grads, opt_state,
                decay_mask=self._decay_mask(params))
        # (1, 1) per shard -> (dp*ep, sp) global: each shard's chunk mean.
        return params, opt_state, local_mean.reshape(1, 1)

    def route_stats(self, state: LMTrainState, tokens):
        """Routing-health counters on the CURRENT weights: per MoE layer
        a dict of ``dropped_frac`` (fraction of routed assignments that
        overflowed expert capacity and rode the residual), ``expert_load``
        (per-expert fraction of kept assignments — the load histogram)
        and ``imbalance`` (max load x E; 1.0 = perfectly balanced).
        ``[]`` for dense models.

        Runs OUTSIDE the train step, on the canonical gathered params
        with every partition axis stripped — one deterministic trunk
        pass (no dropout), cheap at probe cadence and layout-independent:
        a replicated, tp/ep-sharded, ZeRO or FSDP trainer reports the
        same numbers for the same weights and tokens."""
        if not self.model.moe_experts:
            return []
        params = self.params_to_host(state)
        model = dataclasses.replace(
            self.model, sp_axis=None, sp_size=1, tp_axis=None, tp_size=1,
            ep_axis=None, ep_size=1)
        stats = model.route_stats(
            params, jnp.asarray(np.asarray(tokens), jnp.int32))
        return [{k: np.asarray(v) for k, v in layer.items()}
                for layer in stats]

    def _put_batch(self, inputs, targets):
        inputs = np.ascontiguousarray(inputs, np.int32)
        targets = np.ascontiguousarray(targets, np.int32)
        b, L = inputs.shape
        gb = self._global_batch(b, self.dp * self.ep)
        if gb % (self.dp * self.ep):
            raise ValueError(f"global batch {gb} not divisible by dp*ep="
                             f"{self.dp * self.ep}")
        if (gb // (self.dp * self.ep)) % self.grad_accum:
            raise ValueError(
                f"per-shard batch {gb // (self.dp * self.ep)} not "
                f"divisible by grad_accum={self.grad_accum}")
        if L % self.sp:
            raise ValueError(f"seq len {L} not divisible by sp={self.sp}")
        return (self._put_sharded(inputs, self._batch_sharding),
                self._put_sharded(targets, self._batch_sharding))


class PipelineLMTrainer(_MeshTrainer):
    """GPipe-style pipeline engine over a dp x pp (x tp) mesh.

    The layer stack shards into ``pp`` stages (stacked block params,
    tpu_ddp/parallel/pipeline.py); each dp slice's batch is split into
    ``num_micro`` microbatches that stream through the stage ring.
    Composes with tensor parallelism (mp > 1), sequence parallelism
    (sp > 1, round 4: each microbatch's activations hold their L/sp
    chunk and attention inside every stage runs ring K/V rotation or
    Ulysses all-to-all over ``sp`` — the same in-block collectives the
    dense trunk uses, orthogonal to the stage ring over ``pp``),
    dropout (keys derive from (microbatch, global layer), so masks are
    pipeline-geometry-independent), expert parallelism (ep > 1, round 5:
    experts shard over ``ep`` within each stage — the MoE all_to_all
    runs inside the stage's blocks, orthogonal to the stage ring, and
    tokens are data-parallel over dp x ep), and ZeRO-1/2 optimizer-state
    sharding (``opt_sharding="zero1"``: stacked leaves' state laid out
    P((pp, dp)) — P((pp, mp, dp)) with stage-internal tp;
    ``"zero2"``, 1F1B only: additionally reduce-scatters each tick's
    block-gradient contribution over dp so the accumulation carry holds
    1/dp f32 slices) and FSDP within each stage
    (``param_sharding="fsdp"``, round 5: stacked leaves live as
    P((pp[, mp], dp)) flat dp shards, gathered per step — parameter AND
    optimizer memory 1/dp at rest). Gradient accumulation needs no
    separate
    mechanism here: ``num_micro`` IS accumulation — every microbatch's
    gradient sums into one optimizer step, and raising it shrinks both
    per-microbatch activation memory and (under 1F1B, where residency
    is O(pp) regardless) the bubble — so the LMTrainer's ``grad_accum``
    knob maps to ``num_micro`` under the pipeline.
    """

    def __init__(self, model, mesh: Mesh, num_micro: int | None = None,
                 optimizer: AdamW | None = None, dropout_seed: int = 0,
                 schedule: str = "gpipe",
                 opt_sharding: str = "replicated",
                 param_sharding: str = "replicated",
                 clip_grad_norm: float | None = None,
                 sp_mode: str = "ring", pp_virtual: int = 1):
        from tpu_ddp.parallel.pipeline import pipeline_param_specs
        if clip_grad_norm is not None and clip_grad_norm <= 0:
            raise ValueError(f"clip_grad_norm must be > 0, got "
                             f"{clip_grad_norm}")
        self.clip_grad_norm = clip_grad_norm
        self.mesh = mesh
        self.dp = mesh.shape[DATA_AXIS]
        self.pp = mesh.shape[PIPE_AXIS]
        self.tp = mesh.shape.get(MODEL_AXIS, 1)
        self.sp = mesh.shape[SEQ_AXIS]
        self.ep = mesh.shape.get(EXPERT_AXIS, 1)
        if sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sequence-parallel mode {sp_mode!r};"
                             " expected 'ring' or 'ulysses'")
        if model.num_layers % self.pp:
            raise ValueError(f"num_layers={model.num_layers} not "
                             f"divisible by pp={self.pp}")
        if self.sp > 1:
            model = model.with_sequence_parallel(SEQ_AXIS, self.sp,
                                                 mode=sp_mode)
        if self.tp > 1:
            model = model.with_tensor_parallel(MODEL_AXIS, self.tp)
        if self.ep > 1:
            # Round-5: experts shard over ep WITHIN each pp stage — the
            # MoE layer's all_to_all runs inside the stage's blocks,
            # orthogonal to the stage ring over pp (exactly as the
            # pp x sp composition runs ring attention inside stages).
            # Tokens are data-parallel over (dp x ep), so the batch
            # axis shards over both; stacked expert leaves' specs gain
            # the ep axis (P(pp, ep, ...)) via pipeline_param_specs.
            model = model.with_expert_parallel(EXPERT_AXIS, self.ep)
        self.model = model
        self.num_micro = num_micro if num_micro is not None else self.pp
        self.optimizer = optimizer or AdamW()
        # "gpipe": all-forwards-then-all-backwards via AD of the tick
        # scan — activation residency O(num_micro). "1f1b": hand-
        # scheduled one-forward-one-backward with recompute-vjp —
        # residency O(pp), the long-batch memory lever
        # (tpu_ddp/parallel/pipeline.py:pipeline_1f1b_grads).
        # "interleaved": 1F1B with pp_virtual chunks per stage — the
        # bubble shrinks V x for V x more in-flight chunk activations.
        # "zerobubble": 1F1B with the backward split into B-input /
        # B-weight, the weight half deferred off the warmup ticks.
        if schedule not in ("gpipe", "1f1b", "interleaved", "zerobubble"):
            raise ValueError(f"unknown schedule {schedule!r}; choose "
                             "'gpipe', '1f1b', 'interleaved' or "
                             "'zerobubble'")
        self.schedule = schedule
        if pp_virtual < 1:
            raise ValueError(f"pp_virtual must be >= 1, got {pp_virtual}")
        if pp_virtual > 1 and schedule != "interleaved":
            raise ValueError(
                f"pp_virtual={pp_virtual} only applies to "
                "schedule='interleaved' (zero-bubble extends plain 1F1B;"
                " gpipe/1f1b run one chunk per stage)")
        self.pp_virtual = pp_virtual
        self._layer_perm = None
        self._stage_layout = None
        if schedule == "interleaved":
            if model.num_layers % (self.pp * pp_virtual):
                raise ValueError(
                    f"interleaved schedule needs num_layers divisible "
                    f"by pp*pp_virtual: {model.num_layers} % "
                    f"{self.pp * pp_virtual} != 0")
            if self.num_micro % self.pp:
                raise ValueError(
                    f"interleaved schedule needs num_micro divisible "
                    f"by pp: {self.num_micro} % {self.pp} != 0")
            if pp_virtual > 1:
                from tpu_ddp.parallel.pipeline import \
                    interleave_permutation
                self._layer_perm = interleave_permutation(
                    model.num_layers, self.pp, pp_virtual)
                # Rows are re-ordered, so the flat slicing the sharded
                # layouts do is no longer layer-aligned on disk; the
                # plan records the layout so restore/reshard onto a
                # different schedule cannot silently mix layer orders
                # (parallel/redistribute.py:ShardingPlan.stage_layout).
                self._stage_layout = {
                    "kind": "interleaved", "pp": self.pp,
                    "pp_virtual": pp_virtual,
                    "num_layers": model.num_layers}
        if pp_virtual > 1 and (opt_sharding != "replicated"
                               or param_sharding != "replicated"):
            raise ValueError(
                "pp_virtual > 1 re-orders the stacked layer rows "
                "(interleave_permutation); the sharded-optimizer "
                "layouts (zero1/zero2/fsdp) slice those rows flat and "
                "are not permutation-aware — use replicated opt/param "
                "sharding with virtual stages")
        # Per-step dropout keys: seed + step, folded host-side like the
        # LMTrainer's (resume-exact); inert when dropout_rate == 0.
        self._dropout_key = jax.random.key(dropout_seed)
        self._param_specs = pipeline_param_specs(model)
        # ZeRO-1 under pp (round-3 addition): optimizer state for the
        # pp-sharded stacked block leaves is laid out P((pp, dp)) — each
        # stage's slice dp-sharded — via the same partition-aware ZeRO1
        # the LMTrainer uses for tp.
        # ZeRO-2 under pp (round-5): 1F1B's per-tick block-gradient
        # contributions are reduce-scattered over dp immediately and the
        # scan carry holds 1/dp f32 slices — num_micro IS the
        # accumulation here, so this is exactly the regime ZeRO-2's
        # scattered accumulation pays in (arXiv:1910.02054 §5).
        if opt_sharding not in ("replicated", "zero1", "zero2"):
            raise ValueError(f"unknown opt_sharding {opt_sharding!r}; "
                             "choose 'replicated', 'zero1' or 'zero2'")
        # FSDP within each stage (round-5, the last structural gap of
        # the composition matrix): the STACKED block leaves' flat
        # layout is partition-aware over pp (P((pp, [mp/ep,] dp))), so
        # ZeRO3.gather_params reassembles exactly this stage's stacked
        # slice from its dp shards — the leaf the pipeline body
        # expects. Under GPipe the gather sits inside the
        # differentiated function and autodiff's transpose delivers
        # dp-scattered gradient shards (the LMTrainer FSDP trick);
        # under 1F1B (hand-scheduled vjp) the gather runs once at step
        # start and the full stage-local gradients reduce-scatter at
        # the end — parameter and optimizer memory are 1/dp at rest
        # either way.
        if param_sharding not in ("replicated", "fsdp"):
            raise ValueError(f"unknown param_sharding {param_sharding!r}"
                             "; choose 'replicated' or 'fsdp'")
        self.is_fsdp = param_sharding == "fsdp"
        if self.is_fsdp and opt_sharding != "replicated":
            raise ValueError(
                f"opt_sharding={opt_sharding!r} is redundant under "
                "param_sharding='fsdp' (ZeRO-3 already shards the "
                "optimizer state over dp)")
        self.opt_zero1 = opt_sharding in ("zero1", "zero2")
        self.opt_zero2 = opt_sharding == "zero2"
        if self.opt_zero2 and schedule != "1f1b":
            raise ValueError(
                "opt_sharding='zero2' under the pipeline requires "
                "schedule='1f1b': GPipe differentiates the whole tick "
                "scan at once, so there is no per-microbatch gradient "
                "accumulator to scatter — ZeRO-2's memory saving only "
                "exists where the accumulation buffer does")
        from tpu_ddp.ops.optim import Adafactor
        from tpu_ddp.parallel.pipeline import stack_block_params
        if pp_virtual > 1 and isinstance(self.optimizer, Adafactor):
            raise ValueError(
                "pp_virtual > 1 does not compose with Adafactor: the "
                "per-cell factored state has no params-shaped host form "
                "to carry through the interleave permutation at "
                "checkpoint time; use AdamW/SGD with virtual stages")
        if self.opt_zero1:
            from tpu_ddp.parallel.zero import FactoredZeRO1, ZeRO1
            self._params_template = jax.eval_shape(
                lambda: stack_block_params(
                    self.model.init(jax.random.key(0))))
            if isinstance(self.optimizer, Adafactor):
                # Round-5: stacked pp(-and-mp/ep)-sharded leaves compose
                # via PER-CELL factoring — each stage cell factors its
                # own stacked slice, dp row-sharded within the cell
                # (zero.py:FactoredZeRO1 round-5 notes).
                if self.opt_zero2:
                    raise ValueError(
                        "opt_sharding='zero2' (dp-scattered flat "
                        "gradient slices) does not compose with "
                        "Adafactor's row-sharded factored state; use "
                        "'zero1' or an elementwise optimizer")
                if self.clip_grad_norm is not None:
                    raise ValueError(
                        "clip_grad_norm with opt_sharding='zero1' "
                        "Adafactor is not supported (Adafactor already "
                        "clips by update RMS, ops/optim.py); use AdamW/"
                        "SGD or drop the clip")
                self.optimizer = FactoredZeRO1(
                    self.optimizer, DATA_AXIS, self.dp,
                    template=self._params_template,
                    param_specs=self._param_specs,
                    mesh_axis_sizes=dict(mesh.shape))
            else:
                self.optimizer = ZeRO1(
                    self.optimizer, DATA_AXIS, self.dp,
                    template=self._params_template,
                    param_specs=self._param_specs,
                    mesh_axis_sizes=dict(mesh.shape))
        elif isinstance(self.optimizer, Adafactor) and not self.is_fsdp:
            # Round-5: replicated-opt Adafactor under the pipeline — the
            # per-cell layout over the STACKED specs (each stage/mp/ep
            # cell factors its own stacked slice). Wrapped even at
            # pp=1: pipeline_param_specs stamps PIPE_AXIS on block
            # specs unconditionally, so the BARE state_specs would
            # refuse; extent-1 axes partition trivially (parts drop
            # them) and the wrapper degenerates to the bare layout.
            from tpu_ddp.parallel.zero import CellAdafactor
            self.optimizer = CellAdafactor(
                self.optimizer,
                template=jax.eval_shape(
                    lambda: stack_block_params(
                        self.model.init(jax.random.key(0)))),
                param_specs=self._param_specs,
                mesh_axis_sizes=dict(mesh.shape))
        if self.is_fsdp:
            from tpu_ddp.parallel.zero import ZeRO3
            if isinstance(self.optimizer, Adafactor):
                raise ValueError(
                    "param_sharding='fsdp' re-lays leaves out flat, "
                    "which cannot host Adafactor's factored state; use "
                    "AdamW/SGD under fsdp, or opt_sharding='zero1' "
                    "with Adafactor (per-cell FactoredZeRO1)")
            self._params_template = jax.eval_shape(
                lambda: stack_block_params(
                    self.model.init(jax.random.key(0))))
            self._orig_specs = self._param_specs
            self.zero3 = ZeRO3(self.optimizer, DATA_AXIS, self.dp,
                               template=self._params_template,
                               param_specs=self._orig_specs,
                               mesh_axis_sizes=dict(mesh.shape))
            self._param_specs = self.zero3.flat_param_specs()
            self._opt_specs = self.zero3.state_specs()
            # Decay policy on the ORIGINAL per-layer ranks (flat shards
            # are rank-1 and stacked leaves rank+1): proto of one
            # layer's leaves, the _decay_mask trick, precomputed from
            # the template since flat params carry no layer shapes.
            proto = dict(self._params_template)
            proto["blocks"] = jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(m.shape[1:], m.dtype),
                self._params_template["blocks"])
            self._fsdp_decay_mask = self.optimizer.decay_mask(proto)
        else:
            self._opt_specs = self.optimizer.state_specs(
                self._param_specs)
        batch_spec = P((DATA_AXIS, EXPERT_AXIS), SEQ_AXIS)
        self._batch_sharding = NamedSharding(mesh, batch_spec)
        self._param_shardings = self._shardings(self._param_specs)
        self._opt_shardings = self._shardings(self._opt_specs)
        self._train_step = self._compile_step(batch_spec, batch_spec)

    def init_state(self, seed: int = 0) -> LMTrainState:
        """Same seed -> same parameters as the dense model, re-laid-out:
        blocks stacked on a leading layer axis, sharded over pp (and
        under fsdp additionally flattened into dp shards per cell)."""
        from tpu_ddp.parallel.pipeline import (permute_stacked_blocks,
                                               stack_block_params)
        params = stack_block_params(self.model.init(jax.random.key(seed)))
        if self._layer_perm is not None:
            params = permute_stacked_blocks(params, self._layer_perm)
        if self.is_fsdp:
            params = self.zero3.shard_params(params)
            return self._place_state(params, self.zero3.init(params))
        return self._place_state(params, self.optimizer.init(params))

    def _decay_mask(self, params):
        """Evaluate the optimizer's decay policy on the ORIGINAL per-layer
        leaf shapes: stacking raised every block leaf's rank by one, which
        would otherwise weight-decay the (num_layers, dm) LayerNorm
        scales/biases that the dense trainer exempts."""
        proto = dict(params)
        proto["blocks"] = jax.tree.map(lambda p: p[0], params["blocks"])
        return self.optimizer.decay_mask(proto)

    def canonical_params(self, params):
        """Stacked params in DENSE layer order — identity except under
        virtual stages, whose stacked rows live in the
        interleave_permutation order (host or device tree)."""
        if self._layer_perm is None:
            return params
        from tpu_ddp.parallel.pipeline import permute_stacked_blocks
        return permute_stacked_blocks(params,
                                      np.argsort(self._layer_perm))

    def _to_canonical_host(self, params, opt_state):
        """Checkpoints store the DENSE layer order for every schedule:
        unpermute the stacked rows of params AND each params-shaped
        optimizer subtree (map_param_like) so a checkpoint written by an
        interleaved trainer restores into any other schedule."""
        if self._layer_perm is None:
            return params, opt_state
        from tpu_ddp.parallel.pipeline import permute_stacked_blocks
        inv = np.argsort(self._layer_perm)
        fn = lambda t: permute_stacked_blocks(t, inv)  # noqa: E731
        return fn(params), self.optimizer.map_param_like(opt_state, fn)

    def _from_canonical_host(self, params, opt_state):
        if self._layer_perm is None:
            return params, opt_state
        from tpu_ddp.parallel.pipeline import permute_stacked_blocks
        perm = self._layer_perm
        fn = lambda t: permute_stacked_blocks(t, perm)  # noqa: E731
        return fn(params), self.optimizer.map_param_like(opt_state, fn)

    def _sync_grads(self, grads, skip_dp: bool = False, specs=None):
        """Stacked block leaves are stage-local (mean over dp/sp/ep
        only); replicated leaves (embed/head/ln_f) got their real
        gradient on one stage and zeros elsewhere — sum over pp
        reassembles it. Under sequence parallelism every leaf's gradient
        is a partial from this shard's L/sp chunk — the mean over ``sp``
        (with the loss scaled by the (dp, sp, ep) shard count)
        telescopes to the global token mean, the LMTrainer algebra.
        An ep-sharded expert leaf owns its slice (no ep collective) BUT
        its gradient already holds the SUM over every ep token shard's
        contribution (the backward all_to_all delivered them), so its
        mean over ``ep`` is a plain division — LMTrainer._sync_grads'
        excluded-axis algebra.
        ``skip_dp``: ZeRO-1/2 delegate the dp mean to their psum_scatter
        — pp reassembly and the sp/ep means still happen here (under
        ZeRO-2 the block leaves arrive as dp-scattered f32 slices; every
        op here is elementwise or a non-dp collective, and linear ops
        commute with slicing).
        ``specs``: the spec tree matching the GRADS' layout — defaults
        to the trainer's param specs; the fsdp paths pass the ORIGINAL
        (pre-flattening) stacked specs since their algebra runs on
        stage-local leaves or their aligned flat shards."""
        if specs is None:
            specs = self._param_specs

        def leaf(g, spec):
            sharded = _spec_axes(spec)
            if PIPE_AXIS not in sharded:
                g = lax.psum(g, PIPE_AXIS)
            sync = tuple(a for a in (SEQ_AXIS, EXPERT_AXIS)
                         if self.mesh.shape[a] > 1 and a not in sharded)
            if sync:
                g = lax.pmean(g, sync)
            if EXPERT_AXIS in sharded and self.ep > 1:
                g = g / float(self.ep)
            return g if skip_dp else lax.pmean(g, DATA_AXIS)
        return jax.tree.map(leaf, grads, specs)

    def _extra_in_specs(self) -> tuple:
        return (P(),)  # dropout key: replicated on every shard

    def _extra_args(self, state) -> tuple:
        return (jax.random.fold_in(self._dropout_key, state.step),)

    def _decorrelate_rng(self, rng):
        """Distinct dropout keys per dp/sp/ep shard (different tokens);
        the SAME key across pp stages — a microbatch's (mb, layer) mask
        derivation must agree on whichever stage runs that layer — and
        across mp shards (replicated residual stream)."""
        if self.model.dropout_rate <= 0.0:
            return None
        rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
        if self.sp > 1:
            rng = jax.random.fold_in(rng, lax.axis_index(SEQ_AXIS))
        if self.ep > 1:
            rng = jax.random.fold_in(rng, lax.axis_index(EXPERT_AXIS))
        return rng

    def _loss_norm(self, masked_sum, local_n, data_axes):
        """(grad scale, local chunk mean) for one shard's masked loss
        sum — THE loss-normalization algebra, shared by every schedule
        and param layout so the paths cannot drift. Scale by the
        (dp, sp, ep) shard count so the pmean in _sync_grads telescopes
        to the grad of the GLOBAL token mean (the LMTrainer algebra);
        masked_sum is nonzero on the last stage only and the pp-psum in
        _sync_grads completes the sum."""
        total = lax.psum(local_n, data_axes)
        n_shards = lax.psum(1.0, data_axes)
        return n_shards / total, masked_sum / local_n

    def _schedule_grads(self, params, inputs, targets, rng,
                        scatter_blocks=None, blocks_grad_init=None):
        """The hand-scheduled grads function for this trainer's schedule
        — one dispatch point shared by the replicated and fsdp step
        paths. ``skip_invalid`` (interleaved/zerobubble): out-of-range
        ticks cond-skip their chunk compute, safe only when stage bodies
        are collective-free — pure dp x pp; masked execution under
        sp/tp/ep, whose in-block collectives need uniform participation."""
        from tpu_ddp.parallel.pipeline import (
            pipeline_1f1b_grads, pipeline_interleaved_grads,
            pipeline_zerobubble_grads)
        if self.schedule == "1f1b":
            return pipeline_1f1b_grads(
                self.model, params, inputs, targets, pp_size=self.pp,
                num_micro=self.num_micro, rng=rng,
                scatter_blocks=scatter_blocks,
                blocks_grad_init=blocks_grad_init)
        skip = self.sp == 1 and self.tp == 1 and self.ep == 1
        if self.schedule == "interleaved":
            return pipeline_interleaved_grads(
                self.model, params, inputs, targets, pp_size=self.pp,
                num_micro=self.num_micro, pp_virtual=self.pp_virtual,
                rng=rng, skip_invalid=skip)
        return pipeline_zerobubble_grads(
            self.model, params, inputs, targets, pp_size=self.pp,
            num_micro=self.num_micro, rng=rng, skip_invalid=skip)

    def _base_step(self, params, opt_state, inputs, targets, rng):
        from tpu_ddp.parallel.pipeline import pipeline_loss

        rng = self._decorrelate_rng(rng)

        data_axes = ((DATA_AXIS,)
                     + ((SEQ_AXIS,) if self.sp > 1 else ())
                     + ((EXPERT_AXIS,) if self.ep > 1 else ()))
        if self.is_fsdp:
            return self._fsdp_step(params, opt_state, inputs, targets,
                                   rng, data_axes)
        if self.schedule != "gpipe":
            scatter = (self.optimizer.scatter_grads if self.opt_zero2
                       else None)
            masked_sum, local_n, grads = self._schedule_grads(
                params, inputs, targets, rng,
                scatter_blocks=scatter,
                blocks_grad_init=(
                    self.optimizer.shard_zeros(params["blocks"])
                    if self.opt_zero2 else None))
            scale, local_mean = self._loss_norm(masked_sum, local_n,
                                                data_axes)
            # Same normalization the gpipe loss_fn differentiates.
            grads = jax.tree.map(lambda g: g * scale, grads)
        else:
            def loss_fn(p):
                masked_sum, local_n = pipeline_loss(
                    self.model, p, inputs, targets, pp_size=self.pp,
                    num_micro=self.num_micro, rng=rng)
                scale, local_mean = self._loss_norm(masked_sum, local_n,
                                                    data_axes)
                return masked_sum * scale, local_mean

            (_, local_mean), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        # Under ZeRO-1 only the pp half of the sync happens here (the
        # wrapper's psum_scatter is the dp half); one shared apply.
        grads = self._sync_grads(grads, skip_dp=self.opt_zero1)
        if self.opt_zero2:
            # Block slices were scattered per tick inside the 1F1B scan;
            # the small replicated leaves (embed/ln_f/head, now
            # pp-reassembled) scatter once here, and apply_scattered
            # finishes the step (clip on slices, update, all_gather).
            rest = {k: v for k, v in grads.items() if k != "blocks"}
            g_sh = dict(self.optimizer.scatter_grads(rest),
                        blocks=grads["blocks"])
            params, opt_state = self.optimizer.apply_scattered(
                params, g_sh, opt_state,
                decay_mask=self._decay_mask(params),
                clip_norm=self.clip_grad_norm)
        elif self.opt_zero1:
            params, opt_state = self.optimizer.apply(
                params, grads, opt_state,
                decay_mask=self._decay_mask(params),
                clip_norm=self.clip_grad_norm)
        else:
            if self.clip_grad_norm is not None:
                # Stacked leaves are pp(-and-mp)-sharded per their
                # specs; replicated leaves were pp-psum'd just above.
                grads = self._clip_by_global_norm(grads,
                                                  self._param_specs)
            params, opt_state = self.optimizer.apply(
                params, grads, opt_state,
                decay_mask=self._decay_mask(params))
        # Real chunk mean lives on the last stage; share it with everyone
        # (outside the differentiated path). (1, 1) per shard so the
        # out spec P(dp, sp) stacks to a (dp, sp) global.
        mean = lax.psum(local_mean, PIPE_AXIS)
        return params, opt_state, mean.reshape(1, 1)

    def _fsdp_step(self, params, opt_state, inputs, targets, rng,
                   data_axes):
        """FSDP within each stage: ``params`` are flat dp shards of the
        STACKED tree (blocks per (pp[, mp/ep]) cell). GPipe
        differentiates through ``gather_params`` so the AD transpose
        reduce-scatters cotangents into dp shards; 1F1B gathers once at
        step start (hand-scheduled vjp) and reduce-scatters the full
        stage-local gradients afterwards. Either way the non-dp sync
        (pp reassembly of embed/head, sp/ep means) runs with the
        ORIGINAL stacked specs' algebra, aligned shard-by-shard."""
        from tpu_ddp.parallel.pipeline import pipeline_loss

        if self.schedule != "gpipe":
            p_full = self.zero3.gather_params(params)
            masked_sum, local_n, g_full = self._schedule_grads(
                p_full, inputs, targets, rng)
            scale, local_mean = self._loss_norm(masked_sum, local_n,
                                                data_axes)
            g_full = jax.tree.map(lambda g: g * scale, g_full)
            # pp/sp/ep halves of the sync on the full stage-local
            # leaves, then reduce-scatter over dp into the flat shards
            # ZeRO3.apply consumes (scatter_grads yields the dp MEAN).
            g_full = self._sync_grads(g_full, skip_dp=True,
                                      specs=self._orig_specs)
            grads = self.zero3.scatter_grads(g_full)
        else:
            def loss_fn(p_flat):
                masked_sum, local_n = pipeline_loss(
                    self.model, self.zero3.gather_params(p_flat),
                    inputs, targets, pp_size=self.pp,
                    num_micro=self.num_micro, rng=rng)
                scale, local_mean = self._loss_norm(masked_sum, local_n,
                                                    data_axes)
                return masked_sum * scale, local_mean

            (_, local_mean), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # The gather's transpose psum_scatter SUMMED over dp;
            # recover the mean, then run the pp/sp/ep algebra on the
            # flat shards (aligned across pp/sp/ep: same chunking).
            grads = jax.tree.map(lambda g: g / float(self.dp), grads)
            grads = self._sync_grads(grads, skip_dp=True,
                                     specs=self._orig_specs)
        if self.clip_grad_norm is not None:
            # Flat shards: the flat specs carry the (pp[, mp/ep], dp)
            # axes each slice is distinct over.
            grads = self._clip_by_global_norm(grads, self._param_specs)
        params, opt_state = self.zero3.apply(
            params, grads, opt_state, decay_mask=self._fsdp_decay_mask)
        mean = lax.psum(local_mean, PIPE_AXIS)
        return params, opt_state, mean.reshape(1, 1)

    def _put_batch(self, inputs, targets):
        inputs = np.ascontiguousarray(inputs, np.int32)
        targets = np.ascontiguousarray(targets, np.int32)
        b, L = inputs.shape
        gb = self._global_batch(b, self.dp * self.ep)
        if gb % (self.dp * self.ep * self.num_micro):
            raise ValueError(
                f"global batch {gb} not divisible by dp*ep*num_micro="
                f"{self.dp * self.ep * self.num_micro}")
        if L % self.sp:
            raise ValueError(f"seq len {L} not divisible by sp={self.sp}")
        return (self._put_sharded(inputs, self._batch_sharding),
                self._put_sharded(targets, self._batch_sharding))
