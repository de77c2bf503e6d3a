"""The knob audit must pass on the live tree AND catch seeded drift.

A consistency checker that never fails is indistinguishable from one
that checks nothing — every drift class the audit claims to detect is
seeded here with a deliberately-broken registry entry and must produce
a finding that names the problem.
"""

import dataclasses

from scripts.knob_audit import NONPERF_ENV, audit
from tpu_ddp.tune.space import KNOBS, Knob, knob_by_field


def test_live_tree_is_clean():
    # The CI gate: any drift between TrainConfig, the env block, the
    # launch flags, and the registry fails the suite with the audit's
    # own message naming the surface that moved.
    assert audit() == []


def test_catches_missing_field():
    drifted = KNOBS + (Knob("ghost", "no_such_field",
                            "TPU_DDP_DISPATCH_DEPTH", values=(1, 2)),)
    findings = audit(drifted)
    assert any("no_such_field" in f and "does not exist" in f
               for f in findings)


def test_catches_unparsed_env_var():
    # The env var exists in no __post_init__ branch: setting it must
    # leave the field at its default, which the behavioral check flags.
    drifted = KNOBS + (Knob("drift", "dispatch_depth",
                            "TPU_DDP_NO_SUCH_VAR", values=(0, 1, 2, 4)),)
    findings = audit(drifted)
    assert any("TPU_DDP_NO_SUCH_VAR" in f and "not parsed" in f
               for f in findings)


def test_catches_env_wired_to_wrong_field():
    # TPU_DDP_PREFETCH is parsed — but into device_prefetch, not
    # steps_per_dispatch. The probe value lands in the wrong field.
    drifted = KNOBS + (Knob("crossed", "steps_per_dispatch",
                            "TPU_DDP_PREFETCH", values=(1, 4)),)
    findings = audit(drifted)
    assert any("crossed" in f for f in findings)


def test_catches_default_outside_candidates():
    bad = tuple(dataclasses.replace(k, values=(7, 9))
                if k.name == "dispatch_depth" else k for k in KNOBS)
    findings = audit(bad)
    assert any("keep the default" in f for f in findings)


def test_catches_unknown_launch_flag():
    drifted = KNOBS + (Knob("flagless", "dispatch_depth",
                            "TPU_DDP_DISPATCH_DEPTH", values=(0, 2),
                            flag="--no-such-flag"),)
    findings = audit(drifted)
    assert any("--no-such-flag" in f for f in findings)


def test_reverse_check_catches_unregistered_perf_env():
    # Drop the grad_compress entry: config.py still parses
    # TPU_DDP_GRAD_COMPRESS, so the reverse sweep must flag it as a
    # knob living outside the search space.
    pruned = tuple(k for k in KNOBS if k.name != "grad_compress")
    findings = audit(pruned)
    assert any("TPU_DDP_GRAD_COMPRESS" in f and "no registry entry" in f
               for f in findings)


def test_memory_policy_knobs_registered():
    # The two memory-policy knobs (tpu_ddp/memory/) carry the full
    # 4-surface contract; act_dtype changes numerics so it must be
    # semantic (excluded from the default search like compute_dtype),
    # remat must not be (it re-executes the same ops).
    remat = knob_by_field("remat")
    act = knob_by_field("act_dtype")
    assert remat is not None and act is not None
    assert remat.env == "TPU_DDP_REMAT" and remat.flag == "--remat"
    assert act.env == "TPU_DDP_ACT_DTYPE" and act.flag == "--act-dtype"
    assert act.semantic and not remat.semantic
    assert set(remat.values) == {"none", "blocks", "conv_stages", "dots"}
    assert set(act.values) == {"compute", "bf16", "f32"}


def test_moe_knobs_registered():
    # The three MoE knobs (tpu_ddp/parallel/moe.py) carry the full
    # 4-surface contract. All are semantic — each changes WHAT the
    # model computes (a different architecture / routing distribution),
    # so the default step_time search never wanders into them — and all
    # stay under objective="step_time" so the goodput sweeps' exact
    # field sets below are untouched.
    from tpu_ddp.tune.space import Workload, violations

    e = knob_by_field("moe_experts")
    k = knob_by_field("moe_top_k")
    c = knob_by_field("moe_capacity")
    assert e is not None and k is not None and c is not None
    assert e.env == "TPU_DDP_MOE_EXPERTS" and e.flag == "--moe-experts"
    assert k.env == "TPU_DDP_MOE_TOP_K" and k.flag == "--moe-top-k"
    assert c.env == "TPU_DDP_MOE_CAPACITY" and c.flag == "--moe-capacity"
    for knob in (e, k, c):
        assert knob.semantic and knob.objective == "step_time", knob.name
    # Candidate sets include the dense defaults (the audit's
    # keep-the-default rule) and the shipped presets' settings.
    assert 0 in e.values and 1 in k.values and 1.25 in c.values
    # Engine-mirrored violations: an ep mesh needs a MoE model whose
    # expert count it divides; top_k beyond E is a topk_route reject;
    # the routing knobs are inert duplicates of the dense default
    # without experts.
    ep2 = Workload(platform="cpu", ep=2)
    assert violations({"moe_experts": 0}, ep2)
    assert violations({"moe_experts": 5}, ep2)
    assert violations({"moe_experts": 6}, ep2) == []
    assert violations({"moe_experts": 4, "moe_top_k": 8},
                      Workload(platform="cpu"))
    assert violations({"moe_top_k": 2}, Workload(platform="cpu"))
    assert violations({"moe_capacity": 2.0}, Workload(platform="cpu"))
    assert violations({"moe_experts": 4, "moe_top_k": 2,
                       "moe_capacity": 2.0},
                      Workload(platform="cpu")) == []


def test_diloco_knobs_registered():
    # The four DiLoCo knobs (tpu_ddp/train/outer.py, DESIGN.md §29)
    # carry the full 4-surface contract. All are semantic — H local
    # steps between syncs is a different training algorithm, not a
    # schedule — and all stay under objective="step_time" so the
    # goodput sweeps' exact field sets are untouched.
    from tpu_ddp.tune.space import Workload, violations

    h = knob_by_field("diloco_h")
    lr = knob_by_field("outer_lr")
    mu = knob_by_field("outer_momentum")
    wire = knob_by_field("outer_wire")
    assert h is not None and lr is not None
    assert mu is not None and wire is not None
    assert h.env == "TPU_DDP_DILOCO_H" and h.flag == "--diloco-h"
    assert lr.env == "TPU_DDP_DILOCO_OUTER_LR"
    assert lr.flag == "--diloco-outer-lr"
    assert mu.env == "TPU_DDP_DILOCO_OUTER_MOMENTUM"
    assert mu.flag == "--diloco-outer-momentum"
    assert wire.env == "TPU_DDP_DILOCO_OUTER_WIRE"
    assert wire.flag == "--diloco-outer-wire"
    for knob in (h, lr, mu, wire):
        assert knob.semantic and knob.objective == "step_time", knob.name
    # Candidate sets include the off defaults (keep-the-default rule)
    # and the publish wire vocabulary verbatim — the outer wire IS the
    # publish codec, so the sets must not drift apart.
    assert 0 in h.values and 0.7 in lr.values and 0.9 in mu.values
    assert set(wire.values) == {"none", "bf16", "int8", "sparse"}
    # Engine-mirrored violations: the outer knobs are inert duplicates
    # of the plain-sync default without diloco_h, and DiLoCo groups
    # assume the canonical params_to_host layout — pp inside a group
    # is rejected.
    cpu = Workload(platform="cpu")
    assert violations({"outer_lr": 1.0}, cpu)
    assert violations({"outer_momentum": 0.0}, cpu)
    assert violations({"outer_wire": "int8"}, cpu)
    assert violations({"diloco_h": 8, "outer_wire": "int8"}, cpu) == []
    assert violations({"diloco_h": 8}, Workload(platform="cpu", pp=2))


def test_serve_knobs_registered_under_goodput_objective():
    # The serving knobs (tpu_ddp/serve/) carry the same 4-surface
    # contract minus the launch flag (serving is not a launch.py
    # concern), and live under objective="goodput" so the training
    # autotuner's step_time search never wanders into them — and the
    # serve sweep's goodput search gets exactly them.
    from tpu_ddp.tune.space import Workload, searchable_knobs
    from tpu_ddp.utils.config import TrainConfig

    fields = {"serve_slots", "serve_block_size", "serve_prefill_chunk",
              "serve_cache_dtype", "fleet_roles", "prefix_cache",
              "router_policy", "kv_wire",
              # Fleet-resilience knobs (DESIGN.md §23): health and
              # migration in the Router, shedding in the engine.
              "fleet_health", "fleet_probe_backoff_ms",
              "fleet_step_deadline_ms", "fleet_retry_budget",
              "serve_queue_limit", "serve_shed_ms",
              # Weight-streaming knobs (DESIGN.md §24): publish cadence
              # and wire on the trainer, staleness gate across both.
              "publish_every", "publish_wire", "max_staleness_steps",
              # Autoscaling knobs (DESIGN.md §25): replica lifecycle in
              # the Autoscaler, SLO classes in the scheduler's WFQ.
              "fleet_autoscale", "scale_cooldown_ms", "tenant_classes",
              # Speculative decoding + quantized decode (DESIGN.md
              # §26): window width and draft family in the engine,
              # int8 weights at engine construction.
              "spec_k", "spec_draft", "decode_quant",
              # Long-context serving knobs (DESIGN.md §27): tier count
              # and cold codec on the KV pool, context-parallel prefill
              # on the engine's prefill path.
              "kv_tiers", "kv_cold_dtype", "cp_prefill"}
    for f in fields:
        k = knob_by_field(f)
        assert k is not None and k.objective == "goodput", f
    assert knob_by_field("serve_block_size").env == "TPU_DDP_SERVE_BLOCK"
    assert knob_by_field("kv_wire").env == "TPU_DDP_KV_WIRE"
    assert (knob_by_field("fleet_probe_backoff_ms").env
            == "TPU_DDP_FLEET_HEALTH_BACKOFF_MS")
    assert (knob_by_field("max_staleness_steps").env
            == "TPU_DDP_PUBLISH_MAX_STALENESS")
    # Cache dtype and the lossy KV wire change numerics -> semantic,
    # like act_dtype; the pure-scheduling knobs must not be.
    assert knob_by_field("serve_cache_dtype").semantic
    assert knob_by_field("kv_wire").semantic
    assert knob_by_field("publish_wire").semantic
    assert not knob_by_field("publish_every").semantic
    assert not knob_by_field("max_staleness_steps").semantic
    assert not knob_by_field("serve_slots").semantic
    assert not knob_by_field("fleet_roles").semantic
    # Resilience knobs never change what a healthy run computes —
    # migration replay is bitwise (tests/test_fleet_resilience.py) —
    # so none of them are semantic.
    for f in ("fleet_health", "fleet_retry_budget", "serve_queue_limit",
              "serve_shed_ms"):
        assert not knob_by_field(f).semantic, f
    # Autoscaling never changes what any one request computes — drain
    # migration is bitwise and WFQ only reorders admission — so the
    # whole control plane is pure scheduling.
    for f in ("fleet_autoscale", "scale_cooldown_ms", "tenant_classes"):
        assert not knob_by_field(f).semantic, f
    # int8 decode rounds the served logits -> semantic like
    # publish_wire; speculation never changes the emitted stream (the
    # fused families emit only target samples), so spec_k/spec_draft
    # are pure scheduling.
    assert knob_by_field("decode_quant").semantic
    assert not knob_by_field("spec_k").semantic
    assert not knob_by_field("spec_draft").semantic
    assert knob_by_field("spec_k").env == "TPU_DDP_SPEC_K"
    # The int8 cold codec rounds re-read pages -> semantic like
    # kv_wire; the tier count and cp prefill only move/split exact
    # bytes (bitwise parity in tests/test_long_context.py), so both
    # are pure scheduling.
    assert knob_by_field("kv_cold_dtype").semantic
    assert not knob_by_field("kv_tiers").semantic
    assert not knob_by_field("cp_prefill").semantic
    cfg, ctx = TrainConfig(), Workload(platform="cpu")
    good = {k.field for k, _ in
            searchable_knobs(cfg, ctx, objective="goodput",
                             include_semantic=True)}
    # At the default config the coupled fleet knobs collapse to single
    # candidates (kv_wire needs a disagg edge, prefix-affinity needs a
    # cache, the publish wire and gate need a publish cadence, the
    # scale cooldown needs a live autoscaler, a non-default draft needs
    # spec_k > 0 — tune/space.py violations) and drop out of the
    # space; spec_k and decode_quant are live on a single engine.
    # (kv_cold_dtype likewise collapses: it is inert until kv_tiers
    # lifts off 1, while kv_tiers and cp_prefill stay live.)
    assert good == fields - {"router_policy", "kv_wire",
                             "publish_wire", "max_staleness_steps",
                             "scale_cooldown_ms", "spec_draft",
                             "kv_cold_dtype"}
    step = {k.field for k, _ in searchable_knobs(cfg, ctx)}
    assert not (step & fields)
    # With the edge, the cache, a publish cadence, and the autoscaler
    # on, the whole fleet space opens up — EXCEPT speculation, which
    # the disagg decode tier's fused adopt+decode program excludes
    # (spec_k collapses to {0}, which in turn keeps spec_draft inert).
    fleet_cfg = TrainConfig(fleet_roles="disagg", prefix_cache=True,
                            publish_every=1, fleet_autoscale=True)
    good = {k.field for k, _ in
            searchable_knobs(fleet_cfg, ctx, objective="goodput",
                             include_semantic=True)}
    assert good == fields - {"spec_k", "spec_draft", "kv_cold_dtype"}
    # On a single engine with speculation on, the draft family opens.
    spec_cfg = TrainConfig(spec_k=4)
    good = {k.field for k, _ in
            searchable_knobs(spec_cfg, ctx, objective="goodput",
                             include_semantic=True)}
    assert "spec_draft" in good and "decode_quant" in good


def test_reverse_check_catches_unregistered_remat_env():
    # Drop the remat entry: config.py still parses TPU_DDP_REMAT, so
    # the reverse sweep must flag the knob living outside the space.
    pruned = tuple(k for k in KNOBS if k.name != "remat")
    findings = audit(pruned)
    assert any("TPU_DDP_REMAT" in f and "no registry entry" in f
               for f in findings)


def test_catches_junk_accepting_string_env():
    # Seed check (6)'s drift class: a config whose env surface swallows
    # validation errors lets junk land in string fields — the audit
    # must flag every such knob. Seeded by wrapping __post_init__ so
    # the ValueError the validators raise is suppressed (the field
    # keeps the junk the parse branch already wrote).
    from tpu_ddp.utils.config import TrainConfig
    orig = TrainConfig.__post_init__

    def sloppy(self):
        try:
            orig(self)
        except ValueError:
            pass

    TrainConfig.__post_init__ = sloppy
    try:
        findings = audit()
        assert any("knob-audit-junk" in f and "must validate" in f
                   for f in findings)
        assert any("TPU_DDP_REMAT" in f for f in findings)
    finally:
        TrainConfig.__post_init__ = orig


def test_nonperf_allowlist_is_exact():
    # Every allowlisted var must still be absent from the registry —
    # an entry appearing for one means the allowlist line should go.
    registered = {k.env for k in KNOBS}
    assert not (NONPERF_ENV & registered)
    assert knob_by_field("dispatch_depth") is not None
