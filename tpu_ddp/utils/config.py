"""Hyperparameter / run configuration.

The reference keeps every hyperparameter as a module-level constant
(reference part2/part2b/main.py:16-18,177,184-188); we centralise them in one
dataclass so all four parts and the tests share a single source of truth.
"""

from __future__ import annotations

import dataclasses
import os

# Shared seed applied on every node so parameter init is identical across
# replicas — correctness invariant (i) of the reference
# (reference part1/main.py:14,115-117; report §2.2).
SEED = 89395

# Global batch is fixed; per-node batch = global // world_size
# (reference part2/part2b/main.py:177).
GLOBAL_BATCH_SIZE = 256


def _env_bool(name: str, default: bool) -> bool:
    """Parse a boolean env var; unset -> default, junk -> ValueError."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    low = raw.strip().lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"{name}={raw!r}: expected a boolean "
                     f"(1/0/true/false/yes/no/on/off)")


def _env_num(name: str, conv, default):
    """Parse a numeric env var; unset -> default, junk -> ValueError
    naming the variable (a typo'd knob silently running the default
    would be the worst kind of drift)."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return conv(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r}: expected "
                         f"{conv.__name__}") from None


def parse_spec_draft(spec: str) -> tuple[str, int | None]:
    """The ``spec_draft`` grammar, written here once (``TrainConfig``,
    ``tpu_ddp.launch`` and ``ServeEngine`` all call this): ``"self-<j>"``
    (early exit over the target's first j blocks, j >= 1) or ``"quant"``
    (full-depth int8 draft). Returns ("self", j) or ("quant", None);
    raises ValueError on anything else."""
    s = str(spec).strip()
    if s == "quant":
        return "quant", None
    if s.startswith("self-"):
        j = s[len("self-"):]
        if j.isdigit() and int(j) >= 1:
            return "self", int(j)
    if s == "chain":
        raise ValueError(
            "spec_draft='chain' is gone: spec_k=0 already dispatches a "
            "decode step ahead of its harvest (unset TPU_DDP_SPEC_K and "
            "TPU_DDP_SPEC_DRAFT); the drafts for spec_k > 0 are "
            "'self-<j>' and 'quant'")
    raise ValueError(
        f"spec_draft={spec!r}: expected 'self-<j>' (j >= 1) or 'quant' "
        "(TPU_DDP_SPEC_DRAFT)")


@dataclasses.dataclass
class TrainConfig:
    """One training run's configuration (defaults = the reference's)."""

    # Model / data
    model: str = "VGG11"
    num_classes: int = 10
    image_size: int = 32
    in_channels: int = 3
    dataset: str = "cifar10"          # "cifar10" | "imagenet"

    # Optimizer: SGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    # (reference part1/main.py:124-125).
    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4

    # Loop shape (reference part1/main.py:17,128).
    global_batch_size: int = GLOBAL_BATCH_SIZE
    epochs: int = 1
    seed: int = SEED

    # Instrumentation cadence: loss print every 20 iters, timing over
    # iterations 1..39 with iteration 0 discarded as warm-up
    # (reference part1/main.py:82-91).
    log_every: int = 20
    timing_first_iter: int = 1
    timing_last_iter: int = 39

    # TPU-first knobs (no reference equivalent — native to this framework).
    compute_dtype: str = "bfloat16"   # matmul/conv dtype on the MXU
    param_dtype: str = "float32"      # master params & optimizer state
    pallas_sgd: bool = False          # fused Pallas optimizer update kernel
    pallas_bn: bool = False           # fused Pallas BatchNorm+ReLU kernel
    device_prefetch: int = 0          # host->device transfers kept in flight
    # > 1: the epoch loop groups K uniform batches per dispatch via
    # Trainer.build_multi_step (one lax.scan over K optimizer steps —
    # amortizes per-dispatch overhead; bit-equal to K single steps).
    # Ragged/tail batches and in-loop checkpoint/invariant cadences fall
    # back to the per-step path. Env: TPU_DDP_STEPS_PER_DISPATCH.
    steps_per_dispatch: int = 1
    # Async dispatch window (tpu_ddp/train/pipeline.py): the epoch loop
    # keeps up to this many train steps in flight and harvests results
    # lazily — losses, guard flags, heartbeats and checkpoint cadences
    # are driven from HARVESTED steps, so divergence can surface up to
    # dispatch_depth steps late (docs/DESIGN.md §13). 0 = the reference's
    # fully synchronous loop (forced automatically while chaos injection
    # is active and inside the timing window). Env: TPU_DDP_DISPATCH_DEPTH.
    dispatch_depth: int = 2

    # Gradient wire compression (tpu_ddp/parallel/compress.py): the
    # dtype gradients travel the sync collectives at. "none" (fp32
    # baseline), "bf16" (cast before, fp32-accumulate after — 2x fewer
    # wire bytes), "int8" (blockwise quantization with error-feedback
    # residual — ~4x) or "int8-noef" (ablation without the residual).
    # Env: TPU_DDP_GRAD_COMPRESS. Requires a dp>1 mesh and a syncing
    # strategy; degrades to "none" with a warning otherwise.
    grad_compress: str = "none"

    # Pipeline schedule knobs (round 10; tpu_ddp/parallel/pipeline.py,
    # consumed by examples/lm_train.py's pipeline rung). pp_schedule
    # picks the tick schedule: "gpipe" (AD of the forward scan),
    # "1f1b" (hand-scheduled, O(pp) activation residency),
    # "interleaved" (1F1B with pp_virtual chunks per stage — bubble
    # shrinks V x) or "zerobubble" (backward split B-input/B-weight,
    # weight grads fill the cooldown). pp_microbatches 0 = auto (= pp).
    # pp_virtual > 1 requires pp_schedule="interleaved" and
    # num_layers % (pp * pp_virtual) == 0 — the engine re-validates;
    # tune/space.py mirrors the same constraints as knob violations.
    # Env: TPU_DDP_PP_SCHEDULE / TPU_DDP_PP_MICROBATCHES /
    # TPU_DDP_PP_VIRTUAL.
    pp_schedule: str = "gpipe"
    pp_microbatches: int = 0
    pp_virtual: int = 1

    # Overlapped bucketized gradient collectives
    # (tpu_ddp/parallel/overlap.py): partition the gradient pytree into
    # ~bucket_mb-MiB buckets in reverse-autodiff order and issue each
    # bucket's collective from INSIDE the backward pass (torch DDP's
    # reducer, reference part3/main.py:174), with the 2004.13336-style
    # sharded weight update on the all_reduce/fused rungs. Requires a
    # dp>1 mesh and a replicated syncing rung; degrades to the
    # unbucketed path with a warning otherwise. Env: TPU_DDP_OVERLAP;
    # launch flag --overlap.
    overlap: bool = False
    # Bucket payload target in MiB (torch DDP's bucket_cap_mb; default
    # matches its 25). Only meaningful with overlap on. Env:
    # TPU_DDP_BUCKET_MB; launch flag --bucket-mb.
    bucket_mb: int = 25

    # Memory policy (tpu_ddp/memory/): activation rematerialization.
    # Which model stages recompute in the backward pass instead of
    # saving their interior activations to HBM — "none" (save
    # everything), "blocks" (per residual/transformer block),
    # "conv_stages" (coarser: per resolution stage; conv families
    # only, transformers degrade to "blocks" with a warning) or "dots"
    # (jax.checkpoint_policies.dots_saveable: matmul outputs saved,
    # elementwise recomputed). Env: TPU_DDP_REMAT; launch flag --remat.
    remat: str = "none"
    # Saved-residual dtype at stage boundaries: "compute" (no cast),
    # "bf16" or "f32". Changes what autodiff SAVES, not the arithmetic
    # inside stages (regions cast back to compute_dtype on entry) —
    # semantic when it differs from compute_dtype, so the autotuner
    # treats it like compute_dtype (TPU_DDP_TUNE_SEMANTIC gate).
    # Env: TPU_DDP_ACT_DTYPE; launch flag --act-dtype.
    act_dtype: str = "compute"

    # Autotuning (tpu_ddp/tune/): "off" (default), "cached" (apply a
    # previously searched tuning for this workload fingerprint when the
    # cache has one; defaults-with-warning otherwise — safe to leave on
    # everywhere), or "search" (run measured trials over the knob space,
    # persist the winner, apply it). Env: TPU_DDP_AUTOTUNE; launch flag
    # --autotune. Explicit TPU_DDP_* pins on individual knobs always
    # beat the tuner.
    autotune: str = "off"

    # Graph audit (tpu_ddp/analysis/): "off" (default), "warn"
    # (construction-time donation + precision audit of the jitted step
    # programs, findings surfaced as warnings), or "error" (findings
    # raise GraphAuditError before the engine burns a step). Non-perf
    # — it changes what is checked, never what is executed — so it has
    # no tune/space.py entry (NONPERF_ENV in scripts/knob_audit.py).
    # Env: TPU_DDP_AUDIT; launch flag --audit.
    audit: str = "off"

    # Serving (tpu_ddp/serve/): continuous-batching decode slots — the
    # live-batch width of the jitted whole-bank decode step. Env:
    # TPU_DDP_SERVE_SLOTS.
    serve_slots: int = 8
    # Paged KV-cache block size in tokens (tpu_ddp/serve/kv_pool.py).
    # Env: TPU_DDP_SERVE_BLOCK.
    serve_block_size: int = 16
    # Prefill chunk in tokens: how much of a prompt runs per engine
    # step, bounding how long one long prompt can stall the decode
    # batch. Env: TPU_DDP_SERVE_PREFILL_CHUNK.
    serve_prefill_chunk: int = 32
    # KV-cache storage dtype — the memory-policy vocabulary
    # (tpu_ddp/memory/policy.py ACT_DTYPES): "compute" (no cast),
    # "bf16" or "f32". Semantic when it differs from compute_dtype
    # (rounds the attended history), so the autotuner gates it like
    # act_dtype. Env: TPU_DDP_SERVE_CACHE_DTYPE.
    serve_cache_dtype: str = "compute"

    # Serving fleet (tpu_ddp/fleet/): engine role split — "single"
    # (round-12 engine: prefill + decode in one program pair) or
    # "disagg" (dedicated prefill role streaming finished KV blocks to
    # a decode role over an explicit edge). Env: TPU_DDP_FLEET_ROLES.
    fleet_roles: str = "single"
    # Refcounted shared-prefix KV cache (tpu_ddp/fleet/prefix.py): N
    # requests sharing a system prompt pay ONE prefill. Exactness-
    # preserving (copy-on-write at the first divergent token). Env:
    # TPU_DDP_PREFIX_CACHE.
    prefix_cache: bool = False
    # Multi-replica router policy (tpu_ddp/fleet/router.py):
    # "least-loaded" or "prefix-affinity" (route to the replica whose
    # prefix cache holds the longest match; needs prefix_cache). Env:
    # TPU_DDP_ROUTER_POLICY.
    router_policy: str = "least-loaded"
    # Wire format for the disagg prefill->decode KV-block edge, riding
    # parallel/compress.py's EdgeCodec vocabulary: "none" (dense),
    # "bf16", "int8". Lossy formats round the shipped KV, so the knob
    # is semantic (gated like cache dtype). Env: TPU_DDP_KV_WIRE.
    kv_wire: str = "none"

    # Fleet resilience (tpu_ddp/fleet/resilience.py, docs/DESIGN.md
    # §23). Replica health tracking in the router: a replica raising
    # out of step() goes unhealthy and its in-flight requests migrate
    # to survivors. Env: TPU_DDP_FLEET_HEALTH.
    fleet_health: bool = True
    # Exponential-backoff base for probing an unhealthy replica
    # (doubles per consecutive failure, capped at 30s). Env:
    # TPU_DDP_FLEET_HEALTH_BACKOFF_MS.
    fleet_probe_backoff_ms: float = 200.0
    # Per-replica step() wall-clock deadline; an overrun marks the
    # replica unhealthy like a crash (0 = off — CPU test hosts jitter
    # far past any useful default). Env:
    # TPU_DDP_FLEET_HEALTH_DEADLINE_MS.
    fleet_step_deadline_ms: float = 0.0
    # Times one request may be replayed after replica failures before
    # the router sheds it instead of bouncing it forever. Env:
    # TPU_DDP_FLEET_RETRY_BUDGET.
    fleet_retry_budget: int = 3
    # Bounded admission queue per engine: submits past this depth are
    # shed at the door (0 = unbounded). Env:
    # TPU_DDP_SERVE_QUEUE_LIMIT.
    serve_queue_limit: int = 0
    # Deadline-based shedding: a request still queued (no token, no
    # block) past this many ms is dropped — serving it would only burn
    # capacity on an already-missed SLO (0 = off). Env:
    # TPU_DDP_SERVE_SHED_MS.
    serve_shed_ms: float = 0.0
    # Autoscaling fleet control plane (tpu_ddp/fleet/autoscale.py,
    # docs/DESIGN.md §25): an Autoscaler over the Router boots
    # replicas from the weight-publisher's full-push path under load
    # and drains them via deterministic migration when idle. Env:
    # TPU_DDP_FLEET_AUTOSCALE.
    fleet_autoscale: bool = False
    # Minimum ms between autoscale actions — the cooldown half of the
    # thrash guard (hysteresis streaks are Autoscaler constructor
    # args). Must be > 0: a zero cooldown lets one flash crowd churn
    # boot/drain cycles that burn the capacity scaling should add.
    # Env: TPU_DDP_SCALE_COOLDOWN_MS.
    scale_cooldown_ms: float = 1000.0
    # Tenant SLO classes for weighted fair queueing
    # (tpu_ddp/serve/scheduler.py): comma-separated
    # "name=weight[:deadline_ms[:token_budget]]" entries; empty = one
    # anonymous class, plain FIFO admission. Mirrors
    # scheduler.parse_tenant_classes (the source of truth, which
    # re-validates at engine construction). Env:
    # TPU_DDP_TENANT_CLASSES.
    tenant_classes: str = ""
    # Speculative decoding (tpu_ddp/serve/speculative.py,
    # docs/DESIGN.md §26): proposals verified per engine step
    # (0 = off, the one-token baseline). Env: TPU_DDP_SPEC_K.
    spec_k: int = 0
    # Draft family for speculation: "self-<j>" (early exit over the
    # target's first j blocks) or "quant" (full-depth int8 twin);
    # parse_spec_draft above holds the grammar. Inert at spec_k == 0.
    # Env: TPU_DDP_SPEC_DRAFT.
    spec_draft: str = "self-1"
    # Weight-only int8 decode compute (tpu_ddp/ops/quant.py): "none"
    # serves fp, "int8" quantizes every decode-path projection
    # per-output-channel at engine construction (re-derived on each
    # weight hot-swap). Env: TPU_DDP_DECODE_QUANT.
    decode_quant: str = "none"
    # Tiered KV pool (tpu_ddp/serve/kv_pool.py, docs/DESIGN.md §27):
    # 1 = the single-tier pool unchanged; 2 adds an in-HBM quantized
    # cold tier; 3 adds the host-memory spill tier behind it. Mirrors
    # PagedKVPool (the source of truth, which re-validates at pool
    # construction). Env: TPU_DDP_KV_TIERS.
    kv_tiers: int = 1
    # Cold-page codec for tiers >= 2: "int8" (per-token-row symmetric
    # quantization, parallel/compress.py page_quantize) or "bf16"
    # (plain downcast — lossless when the hot cache dtype is bf16).
    # Inert at kv_tiers == 1. Env: TPU_DDP_KV_COLD_DTYPE.
    kv_cold_dtype: str = "int8"
    # Context-parallel chunked prefill (tpu_ddp/serve/long_context.py):
    # "off", or shard each prefill chunk over the mesh's sp axis with
    # "ring" (K/V rotation, cache-seeded online softmax) or "ulysses"
    # (all-to-all head re-sharding). Needs a serving mesh with sp >= 2.
    # Env: TPU_DDP_CP_PREFILL.
    cp_prefill: str = "off"

    # Live train->serve weight streaming (tpu_ddp/publish/,
    # docs/DESIGN.md §24). Publish a versioned weight update to
    # subscribed serving engines every this many trainer steps
    # (0 = off). Env: TPU_DDP_PUBLISH_EVERY.
    publish_every: int = 0
    # Wire format for the pushed param deltas, riding the same
    # EdgeCodec vocabulary as kv_wire: "none" (dense f32), "bf16",
    # "int8" (error-feedback quantization). Env: TPU_DDP_PUBLISH_WIRE.
    publish_wire: str = "none"
    # How many steps the trainer may run ahead of the slowest
    # subscriber's applied version before its publish gate blocks
    # (0 = unbounded; fully async). Env: TPU_DDP_PUBLISH_MAX_STALENESS.
    max_staleness_steps: int = 0

    # Mixture of experts (tpu_ddp/parallel/moe.py, docs/DESIGN.md §28).
    # Experts per MoE MLP layer (0 = dense models; >0 selects/overrides
    # the routed family — the moe presets in models/transformer.py set
    # it per entry). Env: TPU_DDP_MOE_EXPERTS.
    moe_experts: int = 0
    # Routed experts per token: 1 = Switch, 2 = GShard. The model layer
    # re-validates top_k <= experts where the expert count is known.
    # Env: TPU_DDP_MOE_TOP_K.
    moe_top_k: int = 1
    # Expert capacity factor: slots per expert =
    # ceil(T * capacity * top_k / E). Higher = fewer dropped tokens,
    # more padded compute. Env: TPU_DDP_MOE_CAPACITY.
    moe_capacity: float = 1.25

    # DiLoCo low-communication outer loop (tpu_ddp/train/outer.py,
    # docs/DESIGN.md §29). Inner steps per outer round (0 = off: the
    # outer loop is inert and training traces the plain sync path
    # byte-for-byte). Env: TPU_DDP_DILOCO_H.
    diloco_h: int = 0
    # Outer Nesterov-momentum optimizer over pseudo-gradients
    # (params_start - params_end). lr=1 + momentum=0 is the identity
    # outer optimizer (plain parameter averaging).
    # Envs: TPU_DDP_DILOCO_OUTER_LR / TPU_DDP_DILOCO_OUTER_MOMENTUM.
    outer_lr: float = 0.7
    outer_momentum: float = 0.9
    # Wire format of the cross-group pseudo-gradient exchange — the
    # round-17 publish/ delta codec vocabulary ("none" ships bitwise
    # full tensors; bf16/int8/sparse ship rebased deltas, int8 with
    # per-bucket error feedback). Env: TPU_DDP_DILOCO_OUTER_WIRE.
    outer_wire: str = "none"

    # Test/CI hook: cap iterations per epoch (None = full epoch). Settable
    # via env TPU_DDP_MAX_ITERS so part CLIs can be smoke-tested quickly.
    max_iters: int | None = None
    # Mid-epoch checkpoint cadence in steps (0 = epoch ends only); env
    # TPU_DDP_CKPT_EVERY. Enables resume after mid-epoch failures
    # (tpu_ddp/launch.py:launch_elastic).
    ckpt_every_iters: int = 0
    # Replica-consistency check cadence in steps (0 = off); env
    # TPU_DDP_CHECK_REPLICAS_EVERY (tpu_ddp/utils/invariants.py).
    check_replicas_every: int = 0
    # Step guard (tpu_ddp/resilience/guard.py): skip updates whose loss
    # or global grad-norm is non-finite — the state passes through a
    # bad batch unchanged. On by default (a healthy step is bit-identical
    # to an unguarded one); env TPU_DDP_GUARD=0 disables.
    guard_nonfinite: bool = True
    # Consecutive skipped steps before train_epoch raises
    # TrainingDivergedError (the elastic layer then rolls back to the
    # last checkpoint); env TPU_DDP_GUARD_MAX_BAD.
    guard_max_bad_steps: int = 3
    # Elastic membership (tpu_ddp/resilience/elastic.py): on a rank
    # loss/stall/rejoin, survivors reshard their LIVE TrainState onto a
    # rebuilt mesh (parallel/redistribute.py) instead of the cluster
    # dying into restart-from-checkpoint. Workers only act on it when
    # the launcher also provides the protocol directory
    # (TPU_DDP_ELASTIC_DIR). Env: TPU_DDP_ELASTIC_RESHARD; launch flag
    # --elastic-reshard.
    elastic_reshard: bool = False

    def __post_init__(self):
        if self.max_iters is None:
            env = os.environ.get("TPU_DDP_MAX_ITERS")
            if env:
                self.max_iters = int(env)
        # Smoke-test hook: shrink the global batch (e.g. on the 1-core CPU
        # CI host, where a 256-image VGG step is minutes of compute).
        env_bs = os.environ.get("TPU_DDP_GLOBAL_BATCH")
        if env_bs:
            self.global_batch_size = int(env_bs)
        self.pallas_sgd = _env_bool("TPU_DDP_PALLAS_SGD", self.pallas_sgd)
        self.pallas_bn = _env_bool("TPU_DDP_PALLAS_BN", self.pallas_bn)
        env_pf = os.environ.get("TPU_DDP_PREFETCH")
        if env_pf:
            self.device_prefetch = int(env_pf)
        env_spd = os.environ.get("TPU_DDP_STEPS_PER_DISPATCH")
        if env_spd:
            self.steps_per_dispatch = int(env_spd)
        env_dd = os.environ.get("TPU_DDP_DISPATCH_DEPTH")
        if env_dd:
            self.dispatch_depth = int(env_dd)
        if self.dispatch_depth < 0:
            raise ValueError(
                f"dispatch_depth must be >= 0, got {self.dispatch_depth} "
                "(0 = synchronous loop)")
        env_gc = os.environ.get("TPU_DDP_GRAD_COMPRESS")
        if env_gc:
            self.grad_compress = env_gc
        # Mirrors parallel/compress.py SPECS (the source of truth, which
        # re-validates); duplicated so a bad env/config fails HERE with
        # the flag name, not deep inside Trainer construction.
        if self.grad_compress not in ("none", "bf16", "int8",
                                      "int8-noef"):
            raise ValueError(
                f"grad_compress={self.grad_compress!r}: expected "
                "none|bf16|int8|int8-noef (TPU_DDP_GRAD_COMPRESS)")
        env_ps = os.environ.get("TPU_DDP_PP_SCHEDULE")
        if env_ps:
            self.pp_schedule = env_ps
        if self.pp_schedule not in ("gpipe", "1f1b", "interleaved",
                                    "zerobubble"):
            raise ValueError(
                f"pp_schedule={self.pp_schedule!r}: expected "
                "gpipe|1f1b|interleaved|zerobubble (TPU_DDP_PP_SCHEDULE)")
        env_pm = os.environ.get("TPU_DDP_PP_MICROBATCHES")
        if env_pm:
            self.pp_microbatches = int(env_pm)
        if self.pp_microbatches < 0:
            raise ValueError(
                f"pp_microbatches must be >= 0 (0 = auto), got "
                f"{self.pp_microbatches} (TPU_DDP_PP_MICROBATCHES)")
        env_pv = os.environ.get("TPU_DDP_PP_VIRTUAL")
        if env_pv:
            self.pp_virtual = int(env_pv)
        if self.pp_virtual < 1:
            raise ValueError(
                f"pp_virtual must be >= 1, got {self.pp_virtual} "
                "(TPU_DDP_PP_VIRTUAL)")
        # Cross-knob coupling (pp_virtual>1 needs the interleaved
        # schedule, layer divisibility) is enforced where the mesh and
        # model are known: PipelineLMTrainer rejects bad combinations
        # at construction and tune/space.py mirrors them as violations.
        # Validating it here would make each env knob's parse depend on
        # the others', which the single-var audit probes forbid.
        # f32 end-to-end runs turn the bf16-rounding drift story into a
        # measurement (run_experiments --dtype float32): bit-equivalent
        # programs must then agree to f32 reduction-order tolerance.
        env_cd = os.environ.get("TPU_DDP_COMPUTE_DTYPE")
        if env_cd:
            if env_cd not in ("bfloat16", "float32", "float16"):
                raise ValueError(f"TPU_DDP_COMPUTE_DTYPE={env_cd!r}: "
                                 "expected bfloat16|float32|float16")
            self.compute_dtype = env_cd
        # Learning-rate override: the tamed ladder-agreement run
        # (run_experiments --tame) drops lr to 1e-3 so reduction-order
        # noise is not amplified by the lr-0.1 batch-stats-BN dynamics
        # (EXPERIMENTS.md §6 measured ~4x/iter amplification at 0.1).
        env_lr = os.environ.get("TPU_DDP_LR")
        if env_lr:
            lr = float(env_lr)
            if not lr > 0:  # also rejects NaN
                raise ValueError(f"TPU_DDP_LR={env_lr!r}: expected a "
                                 "positive learning rate")
            self.learning_rate = lr
        env_ck = os.environ.get("TPU_DDP_CKPT_EVERY")
        if env_ck:
            self.ckpt_every_iters = int(env_ck)
        env_rc = os.environ.get("TPU_DDP_CHECK_REPLICAS_EVERY")
        if env_rc:
            self.check_replicas_every = int(env_rc)
        self.guard_nonfinite = _env_bool("TPU_DDP_GUARD",
                                         self.guard_nonfinite)
        env_gb = os.environ.get("TPU_DDP_GUARD_MAX_BAD")
        if env_gb:
            self.guard_max_bad_steps = int(env_gb)
        self.elastic_reshard = _env_bool("TPU_DDP_ELASTIC_RESHARD",
                                         self.elastic_reshard)
        self.overlap = _env_bool("TPU_DDP_OVERLAP", self.overlap)
        env_bm = os.environ.get("TPU_DDP_BUCKET_MB")
        if env_bm:
            self.bucket_mb = int(env_bm)
        if self.bucket_mb <= 0:
            raise ValueError(
                f"bucket_mb must be > 0, got {self.bucket_mb} "
                "(TPU_DDP_BUCKET_MB)")
        env_rm = os.environ.get("TPU_DDP_REMAT")
        if env_rm:
            self.remat = env_rm
        env_ad = os.environ.get("TPU_DDP_ACT_DTYPE")
        if env_ad:
            self.act_dtype = env_ad
        # Mirrors tpu_ddp/memory/policy.py (the source of truth, which
        # re-validates at model construction); duplicated so a bad
        # env/config fails HERE with the env-var name.
        if self.remat not in ("none", "blocks", "conv_stages", "dots"):
            raise ValueError(
                f"remat={self.remat!r}: expected "
                "none|blocks|conv_stages|dots (TPU_DDP_REMAT)")
        if self.act_dtype not in ("compute", "bf16", "f32"):
            raise ValueError(
                f"act_dtype={self.act_dtype!r}: expected "
                "compute|bf16|f32 (TPU_DDP_ACT_DTYPE)")
        env_at = os.environ.get("TPU_DDP_AUTOTUNE")
        if env_at:
            self.autotune = env_at
        if self.autotune not in ("off", "cached", "search"):
            raise ValueError(
                f"autotune={self.autotune!r}: expected off|cached|search "
                "(TPU_DDP_AUTOTUNE)")
        env_audit = os.environ.get("TPU_DDP_AUDIT")
        if env_audit:
            self.audit = env_audit
        if self.audit not in ("off", "warn", "error"):
            raise ValueError(
                f"audit={self.audit!r}: expected off|warn|error "
                "(TPU_DDP_AUDIT)")
        env_ss = os.environ.get("TPU_DDP_SERVE_SLOTS")
        if env_ss:
            self.serve_slots = int(env_ss)
        if self.serve_slots < 1:
            raise ValueError(f"serve_slots must be >= 1, got "
                             f"{self.serve_slots} (TPU_DDP_SERVE_SLOTS)")
        env_sb = os.environ.get("TPU_DDP_SERVE_BLOCK")
        if env_sb:
            self.serve_block_size = int(env_sb)
        if self.serve_block_size < 1:
            raise ValueError(
                f"serve_block_size must be >= 1, got "
                f"{self.serve_block_size} (TPU_DDP_SERVE_BLOCK)")
        env_sp = os.environ.get("TPU_DDP_SERVE_PREFILL_CHUNK")
        if env_sp:
            self.serve_prefill_chunk = int(env_sp)
        if self.serve_prefill_chunk < 1:
            raise ValueError(
                f"serve_prefill_chunk must be >= 1, got "
                f"{self.serve_prefill_chunk} "
                "(TPU_DDP_SERVE_PREFILL_CHUNK)")
        env_sc = os.environ.get("TPU_DDP_SERVE_CACHE_DTYPE")
        if env_sc:
            self.serve_cache_dtype = env_sc
        # Mirrors tpu_ddp/memory/policy.py ACT_DTYPES (the source of
        # truth, which re-validates at pool construction).
        if self.serve_cache_dtype not in ("compute", "bf16", "f32"):
            raise ValueError(
                f"serve_cache_dtype={self.serve_cache_dtype!r}: expected "
                "compute|bf16|f32 (TPU_DDP_SERVE_CACHE_DTYPE)")
        env_fr = os.environ.get("TPU_DDP_FLEET_ROLES")
        if env_fr:
            self.fleet_roles = env_fr
        if self.fleet_roles not in ("single", "disagg"):
            raise ValueError(
                f"fleet_roles={self.fleet_roles!r}: expected "
                "single|disagg (TPU_DDP_FLEET_ROLES)")
        self.prefix_cache = _env_bool("TPU_DDP_PREFIX_CACHE",
                                      self.prefix_cache)
        env_rp = os.environ.get("TPU_DDP_ROUTER_POLICY")
        if env_rp:
            self.router_policy = env_rp
        if self.router_policy not in ("least-loaded", "prefix-affinity"):
            raise ValueError(
                f"router_policy={self.router_policy!r}: expected "
                "least-loaded|prefix-affinity (TPU_DDP_ROUTER_POLICY)")
        env_kw = os.environ.get("TPU_DDP_KV_WIRE")
        if env_kw:
            self.kv_wire = env_kw
        # Mirrors parallel/compress.py EdgeCodec wire kinds (the
        # source of truth, which re-validates at edge construction).
        if self.kv_wire not in ("none", "bf16", "int8"):
            raise ValueError(
                f"kv_wire={self.kv_wire!r}: expected none|bf16|int8 "
                "(TPU_DDP_KV_WIRE)")
        self.fleet_health = _env_bool("TPU_DDP_FLEET_HEALTH",
                                      self.fleet_health)
        self.fleet_probe_backoff_ms = _env_num(
            "TPU_DDP_FLEET_HEALTH_BACKOFF_MS", float,
            self.fleet_probe_backoff_ms)
        if self.fleet_probe_backoff_ms <= 0:
            raise ValueError(
                f"fleet_probe_backoff_ms must be > 0, got "
                f"{self.fleet_probe_backoff_ms} "
                "(TPU_DDP_FLEET_HEALTH_BACKOFF_MS)")
        self.fleet_step_deadline_ms = _env_num(
            "TPU_DDP_FLEET_HEALTH_DEADLINE_MS", float,
            self.fleet_step_deadline_ms)
        if self.fleet_step_deadline_ms < 0:
            raise ValueError(
                f"fleet_step_deadline_ms must be >= 0, got "
                f"{self.fleet_step_deadline_ms} "
                "(TPU_DDP_FLEET_HEALTH_DEADLINE_MS)")
        self.fleet_retry_budget = _env_num(
            "TPU_DDP_FLEET_RETRY_BUDGET", int, self.fleet_retry_budget)
        if self.fleet_retry_budget < 0:
            raise ValueError(
                f"fleet_retry_budget must be >= 0, got "
                f"{self.fleet_retry_budget} (TPU_DDP_FLEET_RETRY_BUDGET)")
        self.serve_queue_limit = _env_num(
            "TPU_DDP_SERVE_QUEUE_LIMIT", int, self.serve_queue_limit)
        if self.serve_queue_limit < 0:
            raise ValueError(
                f"serve_queue_limit must be >= 0, got "
                f"{self.serve_queue_limit} (TPU_DDP_SERVE_QUEUE_LIMIT)")
        self.serve_shed_ms = _env_num(
            "TPU_DDP_SERVE_SHED_MS", float, self.serve_shed_ms)
        if self.serve_shed_ms < 0:
            raise ValueError(
                f"serve_shed_ms must be >= 0, got "
                f"{self.serve_shed_ms} (TPU_DDP_SERVE_SHED_MS)")
        self.publish_every = _env_num(
            "TPU_DDP_PUBLISH_EVERY", int, self.publish_every)
        if self.publish_every < 0:
            raise ValueError(
                f"publish_every must be >= 0, got "
                f"{self.publish_every} (TPU_DDP_PUBLISH_EVERY)")
        self.fleet_autoscale = _env_bool("TPU_DDP_FLEET_AUTOSCALE",
                                         self.fleet_autoscale)
        self.scale_cooldown_ms = _env_num(
            "TPU_DDP_SCALE_COOLDOWN_MS", float, self.scale_cooldown_ms)
        if self.scale_cooldown_ms <= 0:
            raise ValueError(
                f"scale_cooldown_ms must be > 0, got "
                f"{self.scale_cooldown_ms} (TPU_DDP_SCALE_COOLDOWN_MS)")
        env_tc = os.environ.get("TPU_DDP_TENANT_CLASSES")
        if env_tc is not None:
            self.tenant_classes = env_tc
        # Mirrors serve/scheduler.py parse_tenant_classes (the source
        # of truth, which re-validates at engine construction): comma-
        # separated name=weight[:deadline_ms[:token_budget]] entries.
        for entry in str(self.tenant_classes).split(","):
            entry = entry.strip()
            if not entry:
                continue
            name, _, rest = entry.partition("=")
            parts = rest.split(":")
            ok = bool(name.strip()) and "=" in entry and \
                1 <= len(parts) <= 3
            if ok:
                try:
                    ok = float(parts[0]) >= 1 and all(
                        float(p) >= 0 for p in parts[1:])
                except ValueError:
                    ok = False
            if not ok:
                raise ValueError(
                    f"tenant_classes entry {entry!r}: expected "
                    "name=weight[:deadline_ms[:token_budget]] "
                    "(TPU_DDP_TENANT_CLASSES)")
        env_pw = os.environ.get("TPU_DDP_PUBLISH_WIRE")
        if env_pw:
            self.publish_wire = env_pw
        # Mirrors publish/publisher.py PUBLISH_WIRES (the publisher
        # re-validates at construction).
        if self.publish_wire not in ("none", "bf16", "int8", "sparse"):
            raise ValueError(
                f"publish_wire={self.publish_wire!r}: expected "
                "none|bf16|int8|sparse (TPU_DDP_PUBLISH_WIRE)")
        self.max_staleness_steps = _env_num(
            "TPU_DDP_PUBLISH_MAX_STALENESS", int,
            self.max_staleness_steps)
        if self.max_staleness_steps < 0:
            raise ValueError(
                f"max_staleness_steps must be >= 0, got "
                f"{self.max_staleness_steps} "
                "(TPU_DDP_PUBLISH_MAX_STALENESS)")
        self.spec_k = _env_num("TPU_DDP_SPEC_K", int, self.spec_k)
        if self.spec_k < 0:
            raise ValueError(
                f"spec_k must be >= 0, got {self.spec_k} "
                "(TPU_DDP_SPEC_K)")
        env_sd = os.environ.get("TPU_DDP_SPEC_DRAFT")
        if env_sd:
            self.spec_draft = env_sd
        parse_spec_draft(self.spec_draft)
        env_dq = os.environ.get("TPU_DDP_DECODE_QUANT")
        if env_dq:
            self.decode_quant = env_dq
        if self.decode_quant not in ("none", "int8"):
            raise ValueError(
                f"decode_quant={self.decode_quant!r}: expected "
                "none|int8 (TPU_DDP_DECODE_QUANT)")
        self.kv_tiers = _env_num("TPU_DDP_KV_TIERS", int, self.kv_tiers)
        if self.kv_tiers not in (1, 2, 3):
            raise ValueError(
                f"kv_tiers must be 1, 2 or 3, got {self.kv_tiers} "
                "(TPU_DDP_KV_TIERS)")
        env_cd = os.environ.get("TPU_DDP_KV_COLD_DTYPE")
        if env_cd:
            self.kv_cold_dtype = env_cd
        if self.kv_cold_dtype not in ("int8", "bf16"):
            raise ValueError(
                f"kv_cold_dtype={self.kv_cold_dtype!r}: expected "
                "int8|bf16 (TPU_DDP_KV_COLD_DTYPE)")
        env_cp = os.environ.get("TPU_DDP_CP_PREFILL")
        if env_cp:
            self.cp_prefill = env_cp
        if self.cp_prefill not in ("off", "ring", "ulysses"):
            raise ValueError(
                f"cp_prefill={self.cp_prefill!r}: expected "
                "off|ring|ulysses (TPU_DDP_CP_PREFILL)")
        self.moe_experts = _env_num(
            "TPU_DDP_MOE_EXPERTS", int, self.moe_experts)
        if self.moe_experts < 0:
            raise ValueError(
                f"moe_experts must be >= 0 (0 = dense), got "
                f"{self.moe_experts} (TPU_DDP_MOE_EXPERTS)")
        self.moe_top_k = _env_num(
            "TPU_DDP_MOE_TOP_K", int, self.moe_top_k)
        if self.moe_top_k < 1:
            raise ValueError(
                f"moe_top_k must be >= 1, got {self.moe_top_k} "
                "(TPU_DDP_MOE_TOP_K)")
        # top_k <= experts needs both knobs; like the pp coupling above,
        # cross-knob checks live in the model layer (topk_route) and in
        # tune/space.py violations, never in the single-var parses.
        self.moe_capacity = _env_num(
            "TPU_DDP_MOE_CAPACITY", float, self.moe_capacity)
        if not self.moe_capacity > 0:  # also rejects NaN
            raise ValueError(
                f"moe_capacity must be > 0, got {self.moe_capacity} "
                "(TPU_DDP_MOE_CAPACITY)")
        self.diloco_h = _env_num(
            "TPU_DDP_DILOCO_H", int, self.diloco_h)
        if self.diloco_h < 0:
            raise ValueError(
                f"diloco_h must be >= 0 (0 = off), got "
                f"{self.diloco_h} (TPU_DDP_DILOCO_H)")
        self.outer_lr = _env_num(
            "TPU_DDP_DILOCO_OUTER_LR", float, self.outer_lr)
        if not self.outer_lr > 0:  # also rejects NaN
            raise ValueError(
                f"outer_lr must be > 0, got {self.outer_lr} "
                "(TPU_DDP_DILOCO_OUTER_LR)")
        self.outer_momentum = _env_num(
            "TPU_DDP_DILOCO_OUTER_MOMENTUM", float, self.outer_momentum)
        if not 0.0 <= self.outer_momentum < 1.0:  # also rejects NaN
            raise ValueError(
                f"outer_momentum must be in [0, 1), got "
                f"{self.outer_momentum} (TPU_DDP_DILOCO_OUTER_MOMENTUM)")
        env_ow = os.environ.get("TPU_DDP_DILOCO_OUTER_WIRE")
        if env_ow:
            self.outer_wire = env_ow
        # Mirrors publish/publisher.py PUBLISH_WIRES (train/outer.py
        # re-validates at OuterLoop construction). diloco_h x pp
        # coupling is a cross-knob rule and lives in tune/space.py
        # violations, like the other couplings above.
        if self.outer_wire not in ("none", "bf16", "int8", "sparse"):
            raise ValueError(
                f"outer_wire={self.outer_wire!r}: expected "
                "none|bf16|int8|sparse (TPU_DDP_DILOCO_OUTER_WIRE)")

    def per_node_batch_size(self, world_size: int) -> int:
        # int(256 / world_size), as in reference part2/part2b/main.py:177.
        return int(self.global_batch_size / world_size)

    @classmethod
    def preset(cls, name: str, **overrides) -> "TrainConfig":
        """Named run configurations (BASELINE.json configs)."""
        try:
            base = dict(PRESETS[name])
        except KeyError:
            raise ValueError(
                f"unknown preset {name!r}; available: {sorted(PRESETS)}"
            ) from None
        base.update(overrides)
        return cls(**base)


# The reference ladder's configuration (configs[0..3]) plus the stretch
# scale-up (configs[4], "ResNet-50 / ImageNet-1k").
PRESETS = {
    "vgg11_cifar10": {},
    "resnet50_imagenet": dict(model="ResNet50", num_classes=1000,
                              image_size=224, dataset="imagenet"),
    "vit_cifar10": dict(model="ViT-tiny"),
}
