"""The perf-knob search space: one declarative registry + constraints.

Every knob the autotuner may turn is ONE entry here, carrying the
``TrainConfig`` field it sets, the ``TPU_DDP_*`` env var that field
parses, the ``python -m tpu_ddp.launch`` flag (when one exists) and the
candidate values trials may measure. ``scripts/knob_audit.py``
cross-checks the four surfaces against each other (and against the
hand-rolled env block in ``utils/config.py``) so they cannot silently
drift — a new knob lands as one registry entry, not N files.
Non-perf control surfaces (TPU_DDP_AUDIT's graph-audit gate, the
elastic protocol plumbing) are deliberately NOT entries: they change
what is *checked* at construction, never what executes, so searching
them would be meaningless — ``knob_audit``'s ``NONPERF_ENV`` allowlist
names them and the reverse sweep keeps the split exact.

The constraint model (:func:`violations`) encodes the combinations the
engine itself refuses or degrades, so the search never spends a trial
on a cell whose measurement would be a lie:

- Pallas kernels compile for the TPU backend only (ops/pallas/);
- ``grad_compress != "none"`` needs a dp>1 mesh AND a syncing rung —
  the Trainer warns and degrades to fp32 otherwise (DESIGN.md §14);
- ``dispatch_depth > 0`` is forced to 0 by the streaming loop when a
  multi-process run carries a collective-bearing in-loop cadence
  (ckpt/replica-digest collectives must enqueue at the same loop
  position on every process — DESIGN.md §13 guard (e));
- ``steps_per_dispatch > 1`` falls back to the per-step path under
  in-loop cadences or ``device_prefetch > 0`` (engine.py), so those
  cells duplicate their per-step twins;
- ``remat`` cells that the memory policy resolves to another cell's
  program are skipped as duplicates: ``conv_stages`` on a transformer
  family degrades to ``blocks``, ``dots`` on a conv family compiles to
  the ``conv_stages`` program (no dot_general inside conv stages), and
  ``act_dtype`` equal to the compute dtype is a no-op cast
  (tpu_ddp/memory/policy.py).

``semantic=True`` marks knobs whose value changes the training
computation itself (dtype, batch size), not just its schedule; the
default space excludes them so tuned runs stay numerically identical
to default runs (opt in with ``TPU_DDP_TUNE_SEMANTIC=1``).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Mapping

__all__ = ["Knob", "KNOBS", "Workload", "Fingerprint", "violations",
           "searchable_knobs", "space_version", "fingerprint_for",
           "workload_for", "knob_by_field", "parse_knob_filter"]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One tunable knob and every surface it must agree across."""

    name: str              # registry name (== the TrainConfig field)
    field: str             # TrainConfig attribute the tuner sets
    env: str               # TPU_DDP_* env var utils/config.py parses
    values: tuple          # candidate values (must include the default)
    flag: str | None = None  # tpu_ddp.launch flag, when one exists
    semantic: bool = False   # changes numerics, not just schedule
    # What a trial measures to compare this knob's candidates:
    # "step_time" (the training objective — every schedule knob) or
    # "goodput" (tokens/sec under a latency SLO, the serving objective
    # measured by tpu_ddp/serve/loadgen.py). The default search space
    # is objective-scoped, so serving knobs never enter a training
    # search and vice versa.
    objective: str = "step_time"
    doc: str = ""

    def encode(self, value) -> str:
        """The env-var string that makes TrainConfig parse ``value`` —
        the round-trip knob_audit drives behaviourally."""
        if isinstance(value, bool):
            return "1" if value else "0"
        return str(value)


# The registry. Values are chosen so the default config is always a
# member (the search must be able to return "keep the defaults") and
# the non-default members are the settings the repo's own sweeps have
# shown to matter (scripts/host_gap.py, EXPERIMENTS.md §9/§10).
KNOBS: tuple[Knob, ...] = (
    Knob("dispatch_depth", "dispatch_depth", "TPU_DDP_DISPATCH_DEPTH",
         values=(0, 1, 2, 4), flag="--dispatch-depth",
         doc="async dispatch window (train/pipeline.py); 0 = sync loop"),
    Knob("steps_per_dispatch", "steps_per_dispatch",
         "TPU_DDP_STEPS_PER_DISPATCH", values=(1, 4, 8),
         doc="K uniform batches per jitted lax.scan dispatch"),
    Knob("device_prefetch", "device_prefetch", "TPU_DDP_PREFETCH",
         values=(0, 2),
         doc="host->device transfers kept in flight (data/prefetch.py)"),
    Knob("grad_compress", "grad_compress", "TPU_DDP_GRAD_COMPRESS",
         values=("none", "bf16", "int8"), flag="--grad-compress",
         doc="gradient wire format on the sync collectives "
             "(parallel/compress.py; int8-noef is an ablation, not a "
             "candidate)"),
    Knob("overlap", "overlap", "TPU_DDP_OVERLAP",
         values=(False, True), flag="--overlap",
         doc="bucketed in-backward gradient collectives + sharded "
             "weight update (parallel/overlap.py); numerics equivalent "
             "to the unbucketed rung up to reduction order, so "
             "searchable by default"),
    Knob("bucket_mb", "bucket_mb", "TPU_DDP_BUCKET_MB",
         values=(1, 4, 25), flag="--bucket-mb",
         doc="bucket payload target in MiB for overlap (torch DDP's "
             "bucket_cap_mb=25 default); smaller buckets start "
             "communicating earlier but amortize less per collective"),
    Knob("pp_schedule", "pp_schedule", "TPU_DDP_PP_SCHEDULE",
         values=("gpipe", "1f1b", "interleaved", "zerobubble"),
         flag="--pp-schedule",
         doc="pipeline tick schedule (parallel/pipeline.py): all four "
             "compute the same step (schedule-equivalence-tested "
             "against the dense model), so the choice is pure "
             "schedule — searchable by default on a pp>1 mesh"),
    Knob("pp_microbatches", "pp_microbatches", "TPU_DDP_PP_MICROBATCHES",
         values=(0, 4, 8, 16), flag="--pp-microbatches",
         doc="microbatches per pipeline step (0 = auto, one per "
             "stage); more microbatches shrink the bubble fraction "
             "but shrink each microbatch's arithmetic intensity"),
    Knob("pp_virtual", "pp_virtual", "TPU_DDP_PP_VIRTUAL",
         values=(1, 2, 4), flag="--pp-virtual",
         doc="virtual stage chunks per physical stage (interleaved "
             "schedule): bubble shrinks V x for V x more in-flight "
             "chunk activations and V x the edge traffic"),
    Knob("pallas_sgd", "pallas_sgd", "TPU_DDP_PALLAS_SGD",
         values=(False, True),
         doc="fused Pallas SGD momentum update kernel (TPU only)"),
    Knob("pallas_bn", "pallas_bn", "TPU_DDP_PALLAS_BN",
         values=(False, True),
         doc="fused Pallas BatchNorm+ReLU kernel (TPU only; model-"
             "level — must be applied before get_model)"),
    Knob("remat", "remat", "TPU_DDP_REMAT",
         values=("none", "blocks", "conv_stages", "dots"), flag="--remat",
         doc="activation rematerialization policy (tpu_ddp/memory/): "
             "recompute stages in the backward pass instead of saving "
             "activations — bytes-for-FLOPs on the HBM wall "
             "(EXPERIMENTS.md §14); numerics-preserving (same ops "
             "re-executed), so searchable by default"),
    Knob("act_dtype", "act_dtype", "TPU_DDP_ACT_DTYPE",
         values=("compute", "bf16", "f32"), flag="--act-dtype",
         semantic=True,
         doc="saved-residual dtype at stage boundaries "
             "(tpu_ddp/memory/); boundaries round-trip through this "
             "dtype, so it changes numerics — searched only with "
             "TPU_DDP_TUNE_SEMANTIC=1"),
    Knob("compute_dtype", "compute_dtype", "TPU_DDP_COMPUTE_DTYPE",
         values=("bfloat16", "float32"), semantic=True,
         doc="matmul/conv dtype; changes the training numerics, so "
             "searched only with TPU_DDP_TUNE_SEMANTIC=1"),
    Knob("global_batch_size", "global_batch_size",
         "TPU_DDP_GLOBAL_BATCH", values=(), semantic=True,
         doc="registered for the audit (field<->env agreement) but "
             "never searched: batch size is a training hyperparameter, "
             "not a schedule knob"),
    Knob("elastic_reshard", "elastic_reshard",
         "TPU_DDP_ELASTIC_RESHARD", values=(),
         flag="--elastic-reshard",
         doc="registered for the audit (field<->env<->flag agreement) "
             "but never searched: live membership resharding "
             "(resilience/elastic.py) is a robustness mode, not a "
             "schedule knob — turning it on cannot change steady-state "
             "step time"),
    # Serving knobs (tpu_ddp/serve/): objective="goodput" scopes them
    # out of the training (step_time) search space and into the
    # serve-sweep/loadgen measurement loop.
    Knob("serve_slots", "serve_slots", "TPU_DDP_SERVE_SLOTS",
         values=(4, 8, 16), objective="goodput",
         doc="continuous-batching decode slots — the live-batch width "
             "of the jitted whole-bank decode step; more slots "
             "amortize weight reads but grow per-step latency"),
    Knob("serve_block_size", "serve_block_size", "TPU_DDP_SERVE_BLOCK",
         values=(8, 16, 32), objective="goodput",
         doc="paged KV-cache block size in tokens (serve/kv_pool.py): "
             "small blocks waste less tail capacity per sequence, "
             "large blocks shrink the table/gather overhead"),
    Knob("serve_prefill_chunk", "serve_prefill_chunk",
         "TPU_DDP_SERVE_PREFILL_CHUNK", values=(16, 32, 64),
         objective="goodput",
         doc="prompt tokens run per engine step: the knob trading "
             "prefill throughput against how long one long prompt can "
             "stall the live decode batch (TTFT tail)"),
    Knob("serve_cache_dtype", "serve_cache_dtype",
         "TPU_DDP_SERVE_CACHE_DTYPE", values=("compute", "bf16", "f32"),
         semantic=True, objective="goodput",
         doc="KV-cache storage dtype (memory-policy vocabulary, "
             "tpu_ddp/memory/policy.py): 'bf16' under an f32 compute "
             "model halves cache reads but rounds the attended "
             "history — semantic, gated like act_dtype"),
    # Fleet knobs (tpu_ddp/fleet/): the serving-fleet layer on top of
    # the engine — same "goodput" objective, measured by the same
    # loadgen harness.
    Knob("fleet_roles", "fleet_roles", "TPU_DDP_FLEET_ROLES",
         values=("single", "disagg"), objective="goodput",
         doc="engine role split: 'disagg' runs a dedicated prefill "
             "role streaming finished KV blocks to a decode role over "
             "an explicit edge (fleet/disagg.py), so long prefills "
             "never steal decode-batch steps"),
    Knob("prefix_cache", "prefix_cache", "TPU_DDP_PREFIX_CACHE",
         values=(False, True), objective="goodput",
         doc="refcounted shared-prefix KV cache (fleet/prefix.py): "
             "requests sharing a system prompt pay one prefill; "
             "exactness-preserving via copy-on-write, so searchable "
             "without a semantic gate"),
    Knob("router_policy", "router_policy", "TPU_DDP_ROUTER_POLICY",
         values=("least-loaded", "prefix-affinity"),
         objective="goodput",
         doc="multi-replica routing (fleet/router.py): "
             "'prefix-affinity' sends a request to the replica whose "
             "prefix cache holds its longest match (cache hit-rate "
             "over pure load spreading); needs prefix_cache"),
    Knob("kv_wire", "kv_wire", "TPU_DDP_KV_WIRE",
         values=("none", "bf16", "int8"), semantic=True,
         objective="goodput",
         doc="disagg prefill->decode edge wire format "
             "(parallel/compress.py EdgeCodec): 'bf16'/'int8' shrink "
             "the shipped KV payload but round it — semantic, gated "
             "like serve_cache_dtype"),
    # Fleet-resilience knobs (fleet/resilience.py, DESIGN.md §23):
    # replica health + migration are Router concerns, shedding is an
    # engine admission concern — all measured by the same loadgen
    # goodput harness (a shed request's tokens are not good tokens).
    Knob("fleet_health", "fleet_health", "TPU_DDP_FLEET_HEALTH",
         values=(False, True), flag="--fleet-health",
         objective="goodput",
         doc="replica health tracking in the Router "
             "(fleet/router.py): step exceptions and deadline "
             "overruns mark a replica unhealthy, its in-flight "
             "requests migrate deterministically, and probe "
             "re-admission follows exponential backoff; off = "
             "fail-fast (a replica exception propagates)"),
    Knob("fleet_probe_backoff_ms", "fleet_probe_backoff_ms",
         "TPU_DDP_FLEET_HEALTH_BACKOFF_MS",
         values=(50.0, 200.0, 1000.0), flag="--fleet-probe-backoff-ms",
         objective="goodput",
         doc="initial probe-re-admission backoff for an unhealthy "
             "replica, doubling per consecutive failure (capped): "
             "short backoff re-admits flapping replicas faster but "
             "burns steps probing a dead one"),
    Knob("fleet_step_deadline_ms", "fleet_step_deadline_ms",
         "TPU_DDP_FLEET_HEALTH_DEADLINE_MS",
         values=(0.0, 250.0, 1000.0), flag="--fleet-step-deadline-ms",
         objective="goodput",
         doc="per-replica step deadline: a step exceeding this is "
             "treated as a failure (slow replica == dead replica, the "
             "serving mirror of the heartbeat stall detector); 0 "
             "disables the deadline"),
    Knob("fleet_retry_budget", "fleet_retry_budget",
         "TPU_DDP_FLEET_RETRY_BUDGET",
         values=(0, 1, 3), flag="--fleet-retry-budget",
         objective="goodput",
         doc="migrations allowed per request before the Router sheds "
             "it instead of re-queueing (a request that has killed N "
             "replicas is suspect — the serving analog of StepGuard's "
             "max-bad-steps budget)"),
    Knob("serve_queue_limit", "serve_queue_limit",
         "TPU_DDP_SERVE_QUEUE_LIMIT",
         values=(0, 64, 256), flag="--serve-queue-limit",
         objective="goodput",
         doc="bounded admission queue: submits beyond this many "
             "waiting requests are shed at the door (engine.py); 0 = "
             "unbounded. Under overload shedding keeps TTFT of "
             "admitted requests inside the SLO instead of letting the "
             "whole queue miss it"),
    Knob("serve_shed_ms", "serve_shed_ms", "TPU_DDP_SERVE_SHED_MS",
         values=(0.0, 100.0, 500.0), flag="--serve-shed-ms",
         objective="goodput",
         doc="queue-deadline shedding: a request still waiting (no "
             "prefill started) this many ms after submission is shed "
             "(its TTFT SLO is already lost); 0 disables"),
    Knob("publish_every", "publish_every", "TPU_DDP_PUBLISH_EVERY",
         values=(0, 1, 4, 16), flag="--publish-every",
         objective="goodput",
         doc="trainer-step cadence for pushing versioned weight "
             "updates to subscribed serving engines (tpu_ddp/publish/); "
             "0 = off. More frequent pushes keep served weights "
             "fresher but spend decode-step time staging buckets"),
    Knob("publish_wire", "publish_wire", "TPU_DDP_PUBLISH_WIRE",
         values=("none", "bf16", "int8", "sparse"),
         flag="--publish-wire",
         objective="goodput", semantic=True,
         doc="wire format for pushed weight deltas (EdgeCodec "
             "vocabulary). Lossy wires round the served weights, so "
             "the knob is semantic like kv_wire; 'sparse' is lossless "
             "zero-chunk elision (the MoE expert-delta wire)"),
    Knob("max_staleness_steps", "max_staleness_steps",
         "TPU_DDP_PUBLISH_MAX_STALENESS",
         values=(0, 2, 8), flag="--publish-max-staleness",
         objective="goodput",
         doc="steps the trainer may run ahead of the slowest "
             "subscriber before its publish gate blocks; 0 = "
             "unbounded (fully async)"),
    # Autoscaling + multi-tenancy knobs (fleet/autoscale.py,
    # serve/scheduler.py WFQ — DESIGN.md §25): same goodput objective,
    # measured by the day-in-the-life trace harness (loadgen.run_trace).
    Knob("fleet_autoscale", "fleet_autoscale", "TPU_DDP_FLEET_AUTOSCALE",
         values=(False, True), flag="--fleet-autoscale",
         objective="goodput",
         doc="autoscaling replica lifecycle control plane "
             "(fleet/autoscale.py): scale-up boots replicas from the "
             "publisher's full-push path, scale-down drains via "
             "bitwise continuation migration; off = static fleet"),
    Knob("scale_cooldown_ms", "scale_cooldown_ms",
         "TPU_DDP_SCALE_COOLDOWN_MS",
         values=(250.0, 1000.0, 5000.0), flag="--scale-cooldown-ms",
         objective="goodput",
         doc="minimum ms between autoscaler actions: short cooldowns "
             "react faster to a flash crowd but risk boot/drain "
             "thrash at the hysteresis band edge; must be > 0"),
    Knob("tenant_classes", "tenant_classes", "TPU_DDP_TENANT_CLASSES",
         values=("", "gold=3,silver=2,bronze=1"),
         flag="--tenant-classes", objective="goodput",
         doc="SLO classes for multi-tenant serving "
             "(serve/scheduler.py): comma-separated name=weight"
             "[:deadline_ms[:token_budget]]; non-empty switches "
             "admission from FIFO to weighted fair queueing with "
             "lowest-class-first shedding; empty = single-tenant"),
    # Speculative decoding + quantized decode (serve/speculative.py,
    # ops/quant.py — DESIGN.md §26): no cell measures them yet
    # (ROADMAP W5).
    Knob("spec_k", "spec_k", "TPU_DDP_SPEC_K",
         values=(0, 4, 12), flag="--spec-k",
         objective="goodput",
         doc="speculative proposals verified per engine step "
             "(serve/speculative.py); 0 = the one-token baseline. "
             "Larger k emits more tokens per weight read while the "
             "draft is right but wastes compute past the draft's "
             "acceptance horizon"),
    Knob("spec_draft", "spec_draft", "TPU_DDP_SPEC_DRAFT",
         values=("self-1", "quant"), flag="--spec-draft",
         objective="goodput",
         doc="draft family for speculation: 'self-<j>' early-exits "
             "over the target's first j blocks, 'quant' runs a "
             "full-depth int8 twin; both emit only the target's own "
             "samples, one dispatch per step"),
    Knob("decode_quant", "decode_quant", "TPU_DDP_DECODE_QUANT",
         values=("none", "int8"), flag="--decode-quant",
         objective="goodput", semantic=True,
         doc="weight-only int8 decode compute (ops/quant.py): "
             "per-output-channel quantization of every decode-path "
             "projection, dequant fused into the matmul. Rounds the "
             "served logits (bounded by the sweep's 0.25% NLL drift "
             "bar), so the knob is semantic like publish_wire"),
    # Long-context serving (serve/long_context.py, serve/kv_pool.py —
    # DESIGN.md §27): tiered KV residency and context-parallel prefill,
    # measured by scripts/long_context_sweep.py.
    Knob("kv_tiers", "kv_tiers", "TPU_DDP_KV_TIERS",
         values=(1, 2, 3), flag="--kv-tiers",
         objective="goodput",
         doc="KV residency tiers (serve/kv_pool.py): 1 = the flat "
             "single-pool cache, 2 adds an in-HBM cold tier of "
             "quantized pages behind an LRU hot set, 3 adds host-memory "
             "spill with demand promotion so HBM bounds the HOT context "
             "per step, not the TOTAL resident context"),
    Knob("kv_cold_dtype", "kv_cold_dtype", "TPU_DDP_KV_COLD_DTYPE",
         values=("int8", "bf16"), flag="--kv-cold-dtype",
         objective="goodput", semantic=True,
         doc="storage dtype for cold-tier KV pages "
             "(parallel/compress.py page codec): 'int8' halves cold "
             "bytes with per-token-row scales and rounds re-read "
             "attention (semantic), 'bf16' is a lossless downcast when "
             "the hot pool is already bf16. Inert at kv_tiers=1 — "
             "there is no cold tier to store into"),
    Knob("cp_prefill", "cp_prefill", "TPU_DDP_CP_PREFILL",
         values=("off", "ring", "ulysses"), flag="--cp-prefill",
         objective="goodput",
         doc="context-parallel chunked prefill (serve/long_context.py): "
             "shard each prefill chunk's query rows over the sp mesh "
             "axis and run ring or Ulysses attention against the paged "
             "cache, cutting TTFT on long prompts. Requires an sp>=2 "
             "mesh and the single-tier pool (engine rejects tiers>1)"),
    # Mixture-of-experts knobs (parallel/moe.py, DESIGN.md §28): all
    # three change WHAT the model computes (a different architecture /
    # routing distribution, not a schedule), so all are semantic —
    # searched only under TPU_DDP_TUNE_SEMANTIC, like compute_dtype.
    Knob("moe_experts", "moe_experts", "TPU_DDP_MOE_EXPERTS",
         values=(0, 4, 8), flag="--moe-experts", semantic=True,
         doc="experts per MoE MLP layer (0 = dense): param count grows "
             "~linearly in E at per-token FLOPs tracking top_k — the "
             "capability-per-FLOP axis (experiments/moe_sweep.json); "
             "an ep>1 mesh must divide E"),
    Knob("moe_top_k", "moe_top_k", "TPU_DDP_MOE_TOP_K",
         values=(1, 2), flag="--moe-top-k", semantic=True,
         doc="routed experts per token: 1 = Switch routing (raw-prob "
             "gate), 2 = GShard (renormalized gates, shared capacity "
             "queues); topk_route rejects top_k > experts"),
    Knob("moe_capacity", "moe_capacity", "TPU_DDP_MOE_CAPACITY",
         values=(1.0, 1.25, 2.0), flag="--moe-capacity", semantic=True,
         doc="expert capacity factor: slots per expert = "
             "ceil(T * capacity * top_k / E). Higher drops fewer "
             "tokens (the dropped_frac train metric) at more padded "
             "expert compute; changes which tokens the experts see, "
             "so semantic"),
    # DiLoCo outer-loop knobs (train/outer.py, DESIGN.md §29): all
    # four change the training trajectory (H local steps between
    # syncs is a different algorithm, not a schedule), so all are
    # semantic — searched only under TPU_DDP_TUNE_SEMANTIC.
    Knob("diloco_h", "diloco_h", "TPU_DDP_DILOCO_H",
         values=(0, 8, 32), flag="--diloco-h", semantic=True,
         doc="DiLoCo inner steps per outer round (0 = off): each "
             "group runs H local steps, only the outer "
             "pseudo-gradient exchange crosses groups — cross-group "
             "bytes drop ~H x before compression "
             "(experiments/diloco_sweep.json)"),
    Knob("outer_lr", "outer_lr", "TPU_DDP_DILOCO_OUTER_LR",
         values=(0.4, 0.7, 1.0), flag="--diloco-outer-lr",
         semantic=True,
         doc="outer Nesterov learning rate over pseudo-gradients; "
             "1.0 with zero momentum is plain parameter averaging"),
    Knob("outer_momentum", "outer_momentum",
         "TPU_DDP_DILOCO_OUTER_MOMENTUM",
         values=(0.0, 0.9), flag="--diloco-outer-momentum",
         semantic=True,
         doc="outer Nesterov momentum in [0, 1); 0.9 is the DiLoCo "
             "setting that recovers most of the synced-baseline "
             "quality at H-fold fewer syncs"),
    Knob("outer_wire", "outer_wire", "TPU_DDP_DILOCO_OUTER_WIRE",
         values=("none", "bf16", "int8", "sparse"),
         flag="--diloco-outer-wire", semantic=True,
         doc="cross-group pseudo-gradient wire (publish/ delta codec "
             "vocabulary): 'none' ships bitwise full tensors, "
             "bf16/int8 quantize the rebased delta (int8 with "
             "per-bucket error feedback carried across rounds)"),
)

# Model-level knobs are baked into get_model() before the Trainer ever
# sees the config; tune.resolve(model_built=True) must drop them.
MODEL_LEVEL_FIELDS = ("pallas_bn", "compute_dtype")


def knob_by_field(field: str) -> Knob | None:
    for k in KNOBS:
        if k.field == field:
            return k
    return None


def space_version() -> str:
    """Hash of the registry structure: any change to the knob set or a
    knob's candidate values invalidates cached tunings via the
    fingerprint (stale overrides are a miss, never a surprise)."""
    payload = [(k.name, k.field, k.env, k.flag, list(map(str, k.values)),
                k.semantic, k.objective) for k in KNOBS]
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()[:12]


@dataclasses.dataclass(frozen=True)
class Workload:
    """The static context constraints are evaluated against."""

    platform: str = "cpu"          # jax.devices()[0].platform
    dp: int = 1                    # data-parallel slots on the mesh
    processes: int = 1             # jax.process_count()
    strategy: str = "none"         # canonical sync rung
    collective_cadence: bool = False  # in-loop ckpt/replica cadence
    # Model family ("conv" | "attn" | "" unknown): the remat policy's
    # degrade/duplicate rules are family-shaped (tpu_ddp/memory/).
    model_family: str = ""
    # Pipeline context (round 10): stages on the mesh and the model's
    # layer count (0 = unknown) — the interleaved divisibility rule
    # needs both; pp <= 1 scopes the pipeline knobs out entirely.
    pp: int = 1
    model_layers: int = 0
    # Expert-parallel extent on the mesh (round 19): the MoE knob
    # rules need it — ep>1 requires a divisible moe_experts.
    ep: int = 1


def workload_for(cfg, strategy: str = "none", mesh=None) -> Workload:
    """Build the constraint context from live runtime state (imports
    jax lazily so pure space/cache tests never touch the backend)."""
    import jax

    from tpu_ddp.parallel.sync import canonical_strategy

    dp, pp, ep = 1, 1, 1
    if mesh is not None:
        try:
            dp = int(mesh.shape.get("dp", 1))
            pp = int(mesh.shape.get("pp", 1))
            ep = int(mesh.shape.get("ep", 1))
        except Exception:  # noqa: BLE001 — a mesh without named axes
            dp, pp, ep = 1, 1, 1
    from tpu_ddp.memory import family_for_model

    layers = 0
    try:
        from tpu_ddp.models.transformer import make_transformer
        layers = int(make_transformer(cfg.model).num_layers)
    except (ValueError, TypeError):
        pass  # non-transformer family: layer-divisibility rule inert

    return Workload(
        platform=jax.devices()[0].platform,
        dp=dp,
        processes=jax.process_count(),
        strategy=canonical_strategy(strategy),
        collective_cadence=bool(cfg.ckpt_every_iters
                                or cfg.check_replicas_every),
        model_family=family_for_model(cfg.model),
        pp=pp,
        model_layers=layers,
        ep=ep,
    )


def violations(assignment: Mapping, ctx: Workload) -> list[str]:
    """Reasons ``assignment`` (field -> value) is a known-invalid cell
    for ``ctx``; empty list == feasible. Each rule mirrors a guard the
    engine enforces at runtime (cited in the module docstring) — the
    search skips these cells instead of measuring a degraded twin."""
    bad = []
    get = assignment.get
    if ctx.platform != "tpu":
        for field in ("pallas_sgd", "pallas_bn"):
            if get(field):
                bad.append(f"{field}=True requires the TPU backend "
                           f"(platform is {ctx.platform!r})")
    if get("grad_compress", "none") != "none":
        if ctx.dp <= 1 or ctx.strategy == "none":
            bad.append(
                f"grad_compress={get('grad_compress')!r} requires a "
                f"dp>1 mesh and a syncing rung (dp={ctx.dp}, "
                f"strategy={ctx.strategy!r}) — Trainer degrades it to "
                "'none' (DESIGN.md §14)")
    if get("overlap", False) and (ctx.dp <= 1 or ctx.strategy not in
                                  ("gather_scatter", "all_reduce",
                                   "fused")):
        bad.append(
            f"overlap=True requires a dp>1 mesh and a replicated "
            f"syncing rung (dp={ctx.dp}, strategy={ctx.strategy!r}) — "
            "Trainer degrades it to the unbucketed path "
            "(train/engine.py)")
    if get("bucket_mb", 25) != 25 and not get("overlap", False):
        bad.append(
            "bucket_mb is only read by the overlapped path — without "
            "overlap=True this cell duplicates the default")
    if get("dispatch_depth", 0) and ctx.processes > 1 \
            and ctx.collective_cadence:
        bad.append(
            "dispatch_depth>0 with a multi-process collective-bearing "
            "cadence — the streaming loop forces depth 0 "
            "(DESIGN.md §13 guard (e))")
    remat = get("remat", "none")
    if remat == "conv_stages" and ctx.model_family == "attn":
        bad.append(
            "remat='conv_stages' on a transformer family — the model "
            "degrades it to 'blocks' with a warning (tpu_ddp/memory/), "
            "so this cell duplicates the 'blocks' cell")
    if remat == "dots" and ctx.model_family == "conv":
        bad.append(
            "remat='dots' on a conv family — conv stages contain no "
            "dot_general (convs are conv_general_dilated), so the "
            "program is identical to 'conv_stages' (duplicate cell)")
    act = get("act_dtype", "compute")
    cdty = str(get("compute_dtype", "bfloat16"))
    if (act, cdty) in (("bf16", "bfloat16"), ("f32", "float32")):
        bad.append(
            f"act_dtype={act!r} with compute_dtype={cdty!r} — the "
            "boundary cast is a no-op, duplicate of 'compute'")
    scd = get("serve_cache_dtype", "compute")
    if (scd, cdty) in (("bf16", "bfloat16"), ("f32", "float32")):
        bad.append(
            f"serve_cache_dtype={scd!r} with compute_dtype={cdty!r} — "
            "the cache cast is a no-op, duplicate of 'compute' "
            "(tpu_ddp/memory/policy.py resolve_act_dtype)")
    # Fleet knobs (tpu_ddp/fleet/) — mirror the fleet layer's guards.
    kw = get("kv_wire", "none")
    if kw != "none" and get("fleet_roles", "single") != "disagg":
        bad.append(
            f"kv_wire={kw!r} without fleet_roles='disagg' — no edge "
            "exists for the wire format to compress, so the cell "
            "duplicates the default")
    if (get("router_policy", "least-loaded") == "prefix-affinity"
            and not get("prefix_cache", False)):
        bad.append(
            "router_policy='prefix-affinity' without prefix_cache — "
            "every replica reports a zero-length cached prefix, so "
            "routing degenerates to least-loaded (duplicate cell)")
    # Publish knobs (tpu_ddp/publish/) — mirror Publisher's guards.
    if get("publish_every", 0) == 0:
        if get("publish_wire", "none") != "none":
            bad.append(
                f"publish_wire={get('publish_wire')!r} with "
                "publish_every=0 — no push ever encodes, so the cell "
                "duplicates the default")
        if get("max_staleness_steps", 0) != 0:
            bad.append(
                f"max_staleness_steps={get('max_staleness_steps')} "
                "with publish_every=0 — the gate only arms on "
                "publish, so the cell duplicates the default")
    if get("scale_cooldown_ms", 1000.0) != 1000.0 \
            and not get("fleet_autoscale", False):
        bad.append(
            f"scale_cooldown_ms={get('scale_cooldown_ms')} without "
            "fleet_autoscale — the cooldown only gates autoscaler "
            "actions, so the cell duplicates the default")
    # Pipeline knobs (round 10) — mirror PipelineLMTrainer's guards.
    sched = get("pp_schedule", "gpipe")
    virt = get("pp_virtual", 1)
    micro = get("pp_microbatches", 0)
    if ctx.pp <= 1:
        if sched != "gpipe" or virt != 1 or micro != 0:
            bad.append(
                "pipeline knobs off-default on a pp<=1 mesh — no "
                "pipeline rung runs, so every cell duplicates the "
                "default")
    else:
        if virt > 1 and sched != "interleaved":
            bad.append(
                f"pp_virtual={virt} requires pp_schedule='interleaved' "
                f"(got {sched!r}) — PipelineLMTrainer rejects it "
                "(zero-bubble extends plain 1F1B; gpipe/1f1b run one "
                "chunk per stage)")
        if sched == "interleaved" and virt == 1:
            bad.append(
                "pp_schedule='interleaved' with pp_virtual=1 runs the "
                "plain 1F1B tick indices — duplicate of the '1f1b' "
                "cell")
        if (sched == "interleaved" and ctx.model_layers
                and ctx.model_layers % (ctx.pp * virt)):
            bad.append(
                f"interleaved needs num_layers % (pp*pp_virtual) == 0: "
                f"{ctx.model_layers} % {ctx.pp * virt} != 0 — "
                "PipelineLMTrainer rejects it")
        if micro and micro % ctx.pp:
            bad.append(
                f"pp_microbatches={micro} not divisible by "
                f"pp={ctx.pp} — the interleaved schedule rejects it "
                "and the others waste the ragged tail")
    if get("steps_per_dispatch", 1) > 1:
        if get("device_prefetch", 0):
            bad.append("steps_per_dispatch>1 with device_prefetch>0 — "
                       "the engine falls back to the per-step path "
                       "(duplicate of the prefetch-only cell)")
        if ctx.collective_cadence:
            bad.append("steps_per_dispatch>1 with an in-loop cadence — "
                       "the engine falls back to the per-step path")
    # Speculative-decoding knobs (serve/speculative.py §26).
    if get("spec_draft", "self-1") != "self-1" and get("spec_k", 0) == 0:
        bad.append(
            f"spec_draft={get('spec_draft')!r} with spec_k=0 — no "
            "speculative step ever runs, so the draft family is inert "
            "and the cell duplicates the default")
    if get("spec_k", 0) > 0 and get("fleet_roles", "single") == "disagg":
        bad.append(
            f"spec_k={get('spec_k')} with fleet_roles='disagg' — the "
            "disaggregated decode tier runs the fused adopt+decode "
            "program only (fleet/disagg.py); speculation is a "
            "single-engine/router feature")
    # Long-context serving knobs (serve/long_context.py §27).
    if get("kv_cold_dtype", "int8") != "int8" and get("kv_tiers", 1) == 1:
        bad.append(
            f"kv_cold_dtype={get('kv_cold_dtype')!r} with kv_tiers=1 — "
            "the flat pool has no cold tier, so the cold dtype is "
            "inert and the cell duplicates the default")
    if get("cp_prefill", "off") != "off" and get("kv_tiers", 1) > 1:
        bad.append(
            f"cp_prefill={get('cp_prefill')!r} with "
            f"kv_tiers={get('kv_tiers')} — the context-parallel "
            "prefill program gathers pages by flat slot id and the "
            "engine rejects the combination (serve/engine.py); tiered "
            "residency is a decode-side feature")
    # MoE knobs (parallel/moe.py §28) — mirror the model layer's guards.
    experts = get("moe_experts", 0)
    if experts == 0:
        if get("moe_top_k", 1) != 1:
            bad.append(
                f"moe_top_k={get('moe_top_k')} with moe_experts=0 — "
                "no routed layer exists, the knob is inert and the "
                "cell duplicates the dense default")
        if get("moe_capacity", 1.25) != 1.25:
            bad.append(
                f"moe_capacity={get('moe_capacity')} with "
                "moe_experts=0 — no routed layer exists, the knob is "
                "inert and the cell duplicates the dense default")
    else:
        if get("moe_top_k", 1) > experts:
            bad.append(
                f"moe_top_k={get('moe_top_k')} > moe_experts="
                f"{experts} — topk_route rejects it (beyond E the "
                "fully-masked argmax would silently re-route to "
                "expert 0)")
    if ctx.ep > 1:
        if experts == 0:
            bad.append(
                f"ep={ctx.ep} mesh with moe_experts=0 — expert "
                "parallelism requires a MoE model "
                "(with_expert_parallel rejects it)")
        elif experts % ctx.ep:
            bad.append(
                f"moe_experts={experts} not divisible by ep={ctx.ep} "
                "— with_expert_parallel rejects it (each device hosts "
                "E/ep stacked experts)")
    diloco_h = get("diloco_h", 0)
    if diloco_h == 0:
        if get("outer_lr", 0.7) != 0.7:
            bad.append(
                f"outer_lr={get('outer_lr')} with diloco_h=0 — the "
                "outer loop is inert, the knob does nothing and the "
                "cell duplicates the plain-sync default")
        if get("outer_momentum", 0.9) != 0.9:
            bad.append(
                f"outer_momentum={get('outer_momentum')} with "
                "diloco_h=0 — the outer loop is inert, the knob does "
                "nothing and the cell duplicates the plain-sync "
                "default")
        if get("outer_wire", "none") != "none":
            bad.append(
                f"outer_wire={get('outer_wire')!r} with diloco_h=0 — "
                "no outer exchange exists to put on a wire; the cell "
                "duplicates the plain-sync default")
    elif ctx.pp > 1:
        bad.append(
            f"diloco_h={diloco_h} on a pp={ctx.pp} mesh — a pipeline "
            "group's params live stage-sharded and the outer "
            "pseudo-gradient exchange assumes the canonical "
            "params_to_host layout per group; run DiLoCo groups over "
            "dp/fsdp rungs (pp inside a group is future work)")
    return bad


def parse_knob_filter(spec: str | None) -> dict | None:
    """Parse ``TPU_DDP_TUNE_KNOBS``: a comma-separated list of registry
    names, each optionally pinning its candidate values —
    ``"dispatch_depth=0|2,steps_per_dispatch"`` keeps two knobs and
    shrinks the first to {0, 2}. Returns {name: values-or-None}, or
    None when unset. Unknown names raise (a typo must not silently tune
    the full space)."""
    if not spec:
        return None
    out: dict = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        name, _, raw = item.partition("=")
        name = name.strip()
        knob = knob_by_field(name)
        if knob is None:
            raise ValueError(
                f"TPU_DDP_TUNE_KNOBS: unknown knob {name!r}; known: "
                f"{[k.name for k in KNOBS]}")
        if not raw:
            out[name] = None
            continue
        vals = []
        for tok in raw.split("|"):
            tok = tok.strip()
            if knob.values and isinstance(knob.values[0], bool):
                vals.append(tok.lower() in ("1", "true", "yes", "on"))
            elif knob.values and isinstance(knob.values[0], int):
                vals.append(int(tok))
            else:
                vals.append(tok)
        out[name] = tuple(vals)
    return out


def searchable_knobs(cfg, ctx: Workload,
                     include_semantic: bool | None = None,
                     only: dict | None = None,
                     objective: str = "step_time") -> list[tuple]:
    """The live search space for ``cfg`` under ``ctx``: a list of
    ``(knob, candidate_values)`` with the config's CURRENT value always
    first (the search must be able to keep it). Knobs are dropped when
    the constraint model leaves fewer than two candidates (e.g. the
    Pallas knobs off-TPU) or when ``only`` (the parsed
    ``TPU_DDP_TUNE_KNOBS`` filter) excludes them. The space is
    ``objective``-scoped: the training search ("step_time", the
    default every existing caller gets) never sees the serving knobs,
    and a "goodput" search (scripts/serve_sweep.py's tuning section)
    never sees the training schedule. Per-value feasibility is checked
    with the other knobs at their config values; the search re-checks
    full assignments, so coupled constraints stay exact."""
    if include_semantic is None:
        include_semantic = os.environ.get(
            "TPU_DDP_TUNE_SEMANTIC", "") in ("1", "true", "yes", "on")
    if only is None:
        only = parse_knob_filter(os.environ.get("TPU_DDP_TUNE_KNOBS"))
    base = {k.field: getattr(cfg, k.field) for k in KNOBS}
    out = []
    for knob in KNOBS:
        if knob.objective != objective:
            continue
        if only is not None and knob.name not in only:
            continue
        if knob.semantic and not include_semantic:
            continue
        if os.environ.get(knob.env):
            # An explicit TPU_DDP_* pin is the user overriding this
            # knob by hand; the tuner must neither search nor override
            # it (resolve() enforces the same rule for cached entries).
            continue
        values = knob.values
        if only is not None and only[knob.name] is not None:
            values = only[knob.name]
        if not values:
            continue
        current = getattr(cfg, knob.field)
        candidates = [current]
        for v in values:
            if v == current or v in candidates:
                continue
            if not violations({**base, knob.field: v}, ctx):
                candidates.append(v)
        if len(candidates) >= 2:
            out.append((knob, tuple(candidates)))
    return out


@dataclasses.dataclass(frozen=True)
class Fingerprint:
    """The workload identity a tuning is valid for. Any field changing
    — model, data scale, mesh, backend, software version, or the knob
    space itself — keys a different cache entry, so a tuning can never
    be applied to a workload it was not measured on."""

    model: str
    dataset: str
    global_batch_size: int
    mesh_shape: str            # "dp=8,sp=1,..." or "none"
    strategy: str
    processes: int
    platform: str
    device_kind: str
    jax_version: str
    jaxlib_version: str
    space_version: str

    def asdict(self) -> dict:
        return dataclasses.asdict(self)

    def key(self) -> str:
        """Stable cache key: sha256 over the canonical JSON form."""
        return hashlib.sha256(
            json.dumps(self.asdict(), sort_keys=True).encode()
        ).hexdigest()[:16]


def fingerprint_for(cfg, strategy: str = "none", mesh=None) -> Fingerprint:
    import jax
    import jaxlib

    from tpu_ddp.parallel.sync import canonical_strategy

    if mesh is not None:
        mesh_shape = ",".join(f"{axis}={size}"
                              for axis, size in mesh.shape.items())
    else:
        mesh_shape = "none"
    dev = jax.devices()[0]
    return Fingerprint(
        model=cfg.model,
        dataset=cfg.dataset,
        global_batch_size=cfg.global_batch_size,
        mesh_shape=mesh_shape,
        strategy=canonical_strategy(strategy),
        processes=jax.process_count(),
        platform=dev.platform,
        device_kind=dev.device_kind,
        jax_version=jax.__version__,
        jaxlib_version=jaxlib.__version__,
        space_version=space_version(),
    )
