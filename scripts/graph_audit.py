#!/usr/bin/env python
"""Sweep the static graph auditor (tpu_ddp/analysis/) over EVERY jit
surface the repo ships and record, per program, the defect findings
that need no execution to see:

- **donation**: intended ``donate_argnums`` vs the executable's
  ``input_output_alias`` header — a donated-but-unaliased buffer is
  copied every call (the round-10 bug class).
- **precision**: f32-widened collectives under a reduced wire
  (bf16/int8 compression that the compiler silently undid) and f64
  creep anywhere in the program.
- **lockstep determinism**: the collective fingerprint (op, dtype,
  payload bytes, replica groups, program order) of the same config
  lowered twice must be IDENTICAL — SPMD processes compile
  independently and deadlock on the first divergent collective, so a
  nondeterministic lowering is a distributed time bomb even though one
  process runs it fine.

Cells: the six sync rungs (none/gather_scatter/all_reduce/fused/zero/
fsdp) on a tiny VGG at dp=4, the compressed fused rungs (bf16/int8),
the bucketized-overlap rung, both MPMD stage programs at pp=2, the
serving engine's decode + prefill steps, the fleet's adopt-decode
repack, both weight-streaming programs (the publisher's delta pack and
the subscriber's donating apply), the DiLoCo outer-step program, and a
live dp4->dp2 redistribute bracketed by fingerprints of both trainers'
programs.

All claims are compiled-HLO claims, valid on any backend; CI runs a
reduced subset (tests/test_graph_audit.py). Exit 1 on ANY finding.

Writes experiments/graph_audit.json.

    python scripts/graph_audit.py
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

RUNGS = ("none", "gather_scatter", "all_reduce", "fused", "zero",
         "fsdp")
GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)


def _tiny_vgg():
    import jax.numpy as jnp
    from tpu_ddp.models.vgg import VGGModel
    # Two pools -> the probe side 4 collapses to 1x1 at the flatten.
    return VGGModel(name="tiny", cfg=(8, "M", 16, "M"),
                    compute_dtype=jnp.float32)


def _tiny_lm(**kw):
    import jax.numpy as jnp
    from tpu_ddp.models.transformer import make_transformer
    cfg = dict(max_seq_len=64, compute_dtype=jnp.float32)
    cfg.update(kw)
    return make_transformer("TransformerLM-tiny", **cfg)


def _tiny_lm_moe(**kw):
    import jax.numpy as jnp
    from tpu_ddp.models.transformer import make_transformer
    cfg = dict(max_seq_len=64, compute_dtype=jnp.float32)
    cfg.update(kw)
    return make_transformer("TransformerLM-moe-tiny", **cfg)


def _abstract_state(trainer):
    """eval_shape of init_state where traceable, concrete otherwise
    (FSDP shards through host numpy)."""
    import types

    import jax
    try:
        params, opt_state, comp_state = jax.eval_shape(
            lambda: (lambda s: (s.params, s.opt_state, s.comp_state))(
                trainer.init_state()))
        return types.SimpleNamespace(
            params=params, opt_state=opt_state, comp_state=comp_state)
    except jax.errors.TracerArrayConversionError:
        return trainer.init_state()


def _probe_batch(trainer, side=4):
    import jax
    import jax.numpy as jnp
    b = 2 * max(1, trainer._dp)
    return (jax.ShapeDtypeStruct((b, side, side, 3), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.float32))


def _program_audit(name, lower_fn, *, wire=None, exempt_ops=(),
                   donation_min_bytes=1024):
    """One program's cell: lower TWICE (determinism is part of the
    claim), then donation + precision + lockstep over the pair."""
    from tpu_ddp import analysis

    lowered = lower_fn()
    compiled = lowered.compile()
    text = compiled.as_text()
    text2 = lower_fn().compile().as_text()

    fp = analysis.collective_fingerprint(text)
    fp2 = analysis.collective_fingerprint(text2)
    don = analysis.donation_report(lowered, compiled=compiled,
                                   min_bytes=donation_min_bytes)
    prec = analysis.precision_report(text, wire, exempt_ops=exempt_ops)
    findings = (list(don["findings"]) + list(prec["findings"])
                + analysis.lockstep_check({"lower-1": fp, "lower-2": fp2}))
    return {
        "program": name,
        "n_collectives": len(fp),
        "fingerprint": analysis.fingerprint_digest(fp),
        "donated": don["donated"],
        "aliased": don["aliased"],
        "wire": wire,
        "findings": findings,
    }


def audit_train_cell(strategy, grad_compress="none", overlap=False):
    import jax

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    cfg = TrainConfig(grad_compress=grad_compress, overlap=overlap,
                      **({"bucket_mb": 1} if overlap else {}))
    # dp=4 on the virtual 8-device CPU mesh; degrade to what the host
    # has (the bench probe runs this on a 1-chip TPU — donation and
    # precision still audit, the sync collectives just vanish).
    dp = min(4, len(jax.devices()))
    mesh = make_mesh(jax.devices()[:dp], dp=dp)
    trainer = Trainer(_tiny_vgg(), cfg, strategy=strategy, mesh=mesh)
    state = _abstract_state(trainer)
    batch = _probe_batch(trainer)
    wire = cfg.grad_compress if trainer._comp_active else None
    # ZeRO/FSDP/sharded-update all_gather f32 PARAMETERS by design —
    # that is not gradient wire traffic (same carve-out as the gate).
    exempt = ("all-gather",) if (trainer.is_zero or trainer.is_fsdp
                                 or trainer._sharded_update is not None) \
        else ()
    name = f"train/{strategy}" \
        + (f"+{grad_compress}" if grad_compress != "none" else "") \
        + ("+overlap" if overlap else "")
    cell = _program_audit(
        name, lambda: trainer.lower_train_step(state, *batch),
        wire=wire, exempt_ops=exempt)
    cell["dp"] = trainer._dp
    return cell


def audit_mpmd_cells():
    from tpu_ddp.parallel.mpmd import StageProgram, split_stage_params
    from tpu_ddp.parallel.pipeline import stack_block_params

    import jax
    import jax.numpy as jnp

    model = _tiny_lm(max_seq_len=32, num_layers=4)
    params = stack_block_params(model.init(jax.random.key(0)))
    stage_params = split_stage_params(params, 2)
    toks = jnp.zeros((4, 32), dtype=jnp.int32)
    cells = []
    for stage in range(2):
        prog = StageProgram(model, stage, 2, 32)
        if prog.fwd is not None:
            cells.append(_program_audit(
                f"mpmd/stage{stage}-fwd",
                lambda: prog.fwd.lower(stage_params[stage], toks)))
        else:
            x = jnp.zeros((4, 32, model.d_model), dtype=jnp.float32)
            tgt = jnp.zeros((4, 32), dtype=jnp.int32)
            cells.append(_program_audit(
                f"mpmd/stage{stage}-bwd",
                lambda: prog.bwd.lower(stage_params[stage], x, tgt)))
    return cells


def audit_serve_cells():
    import jax

    from tpu_ddp.serve.engine import ServeEngine

    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, **GEOM)
    # Speculative + int8 surfaces (DESIGN.md §26): the fused families
    # and the int8 tree each compile distinct programs, audited here.
    # A quantized params tree has a different treedef, so the int8
    # decode/prefill cells are separate jit cache entries, not
    # retraces.
    spec = ServeEngine(model, params, spec_k=4, spec_draft="self-1",
                       **GEOM)
    specq = ServeEngine(model, params, spec_k=4, spec_draft="quant",
                        decode_quant="int8", **GEOM)
    return [
        _program_audit("serve/decode", engine.lower_decode_step),
        _program_audit("serve/prefill", engine.lower_prefill_step),
        _program_audit("serve/spec-step", spec.lower_spec_step),
        _program_audit("serve/spec-step+quant", specq.lower_spec_step),
        _program_audit("serve/decode+int8", specq.lower_decode_step),
        _program_audit("serve/prefill+int8", specq.lower_prefill_step),
    ]


def audit_long_context_cells():
    """The §27 long-context surfaces: the tiered decode/prefill step
    twins (mixed hot/cold reads through two slot tables), the two
    batched page-movement programs (demote quantizes into donated cold
    buffers; promote dequantizes into donated hot buffers — unaliased
    donation here would copy a whole tier per movement), and the
    context-parallel prefill-chunk program on an sp=2 mesh (its ring
    collectives are the cell's fingerprint)."""
    import jax
    import jax.numpy as jnp

    from tpu_ddp.parallel.mesh import make_mesh, replicated_sharding
    from tpu_ddp.serve.engine import ServeEngine
    from tpu_ddp.serve.kv_pool import _demote_prog, _promote_prog

    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    tiered = ServeEngine(model, params, **GEOM, kv_tiers=3,
                         hbm_blocks=6, cold_blocks=9)
    pool = tiered.pool
    sds = jax.ShapeDtypeStruct
    slots = sds((2,), jnp.int32)
    cells = [
        _program_audit("serve/tiered-decode",
                       tiered.lower_tiered_decode_step),
        _program_audit("serve/tiered-prefill",
                       tiered.lower_tiered_prefill_step),
        _program_audit("kv/demote", lambda: _demote_prog.lower(
            pool.k, pool.v, pool.cold_k, pool.cold_v,
            pool.cold_sk, pool.cold_sv, slots, slots)),
        _program_audit("kv/promote", lambda: _promote_prog.lower(
            pool.k, pool.v, pool.cold_k, pool.cold_v,
            pool.cold_sk, pool.cold_sv, slots, slots)),
    ]
    sp = min(2, len(jax.devices()))
    if sp == 2:
        mesh = make_mesh(jax.devices()[:sp], dp=1, sp=sp)
        rp = jax.device_put(params, replicated_sharding(mesh))
        cp = ServeEngine(model, rp, **GEOM, cp_prefill="ring",
                         mesh=mesh)
        cells.append(_program_audit("serve/cp-prefill-ring",
                                    cp.lower_prefill_step))
    return cells


def audit_fleet_cell():
    import jax

    from tpu_ddp.fleet.disagg import DisaggEngine

    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    fleet = DisaggEngine(model, params, **GEOM)
    return [
        _program_audit("fleet/adopt-decode",
                       lambda: fleet.lower_adopt_decode(2)),
        # Degraded-mode local prefill (DESIGN.md §23): the SAME chunked
        # prefill computation as serve/prefill, but compiled against
        # the DECODE pool's geometry — a distinct program the decode
        # worker runs when the edge or the prefill worker dies.
        _program_audit("fleet/degraded-prefill",
                       fleet.lower_degraded_prefill),
    ]


def audit_publish_cells():
    """Both weight-streaming jit surfaces (tpu_ddp/publish/): the
    trainer-side delta pack and the engine-side donating apply. The
    apply's donation IS the zero-copy flip claim — an unaliased live
    tree would copy the whole model every version."""
    import jax

    from tpu_ddp.publish.publisher import Publisher
    from tpu_ddp.publish.subscriber import Subscriber
    from tpu_ddp.serve.engine import ServeEngine

    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
    pub.ensure_plan(jax.tree.map(lambda x: jax.device_get(x), params))
    engine = ServeEngine(model, params, **GEOM)
    sub = Subscriber(engine)
    return [
        _program_audit("publish/push", pub.lower_push_step),
        _program_audit("publish/apply", sub.lower_apply_step),
    ]


def audit_moe_cells():
    """The §28 MoE surfaces. The routed layer is the one place the repo
    emits a PAIR of ``all_to_all``s inside a single program (token
    dispatch to the expert shards and the combine back,
    tpu_ddp/parallel/moe.py) — exactly the divergent-order deadlock
    class the lockstep auditor hunts, so the dp x ep train step is
    fingerprinted here alongside the cached-MoE decode and prefill
    programs (which carry no collective: decode serves on one device,
    capacity computed from the live bank size)."""
    import jax

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.serve.engine import ServeEngine
    from tpu_ddp.train.lm import LMTrainer, make_lm_batch

    model = _tiny_lm_moe()
    cells = []
    if len(jax.devices()) >= 4:
        mesh = make_mesh(jax.devices()[:4], dp=2, ep=2)
        trainer = LMTrainer(model, mesh)
        state = trainer.init_state()
        import numpy as np
        toks = np.zeros((4, 33), np.int64)
        batch = trainer.put_batch(*make_lm_batch(toks))
        cell = _program_audit(
            "train/moe-dp2ep2",
            lambda: trainer.lower_train_step(state, *batch))
        cell["dp"], cell["ep"] = trainer.dp, trainer.ep
        cells.append(cell)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, **GEOM)
    cells.append(_program_audit("serve/moe-decode",
                                engine.lower_decode_step))
    cells.append(_program_audit("serve/moe-prefill",
                                engine.lower_prefill_step))
    return cells


def audit_diloco_cell():
    """The §29 DiLoCo outer-step surface: the guarded Nesterov program
    every coordinator runs once per outer round
    (tpu_ddp/parallel/diloco.py). It carries no collective — agreement
    is by construction over the digest-pinned down edge — so the cell's
    teeth are donation (start params + outer momentum are donated;
    unaliased donation would copy the whole global tree every round)
    and the lockstep fingerprint of the same (lr, mu) lowered twice."""
    import jax

    from tpu_ddp.parallel.diloco import lower_outer_step

    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    return [_program_audit(
        "diloco/outer-step",
        lambda: lower_outer_step(params, outer_lr=0.7,
                                 outer_momentum=0.9))]


def audit_redistribute_cell():
    """Fingerprint the dp=4 source and dp=2 destination train programs
    around a LIVE redistribute: the two fleets' programs legitimately
    differ (replica groups), so the check is per-program determinism
    plus the redistribute completing bitwise-silently."""
    import jax

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.parallel.redistribute import redistribute_state
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    devices = jax.devices()
    src = Trainer(_tiny_vgg(), TrainConfig(), strategy="fused",
                  mesh=make_mesh(devices[:4], dp=4))
    dst = Trainer(_tiny_vgg(), TrainConfig(), strategy="fused",
                  mesh=make_mesh(devices[:2], dp=2))
    state = src.init_state()
    redist = redistribute_state(state, src, dst)

    cells = []
    for name, tr, st in (("redistribute/src-dp4", src, state),
                         ("redistribute/dst-dp2", dst, redist)):
        batch = _probe_batch(tr)
        cells.append(_program_audit(
            name, lambda: tr.lower_train_step(st, *batch)))
    return cells


def build_cells(only=None):
    """The full sweep as (name, thunk) pairs; ``only`` filters by
    substring so tests can run a cheap subset."""
    specs = []
    for rung in RUNGS:
        specs.append((f"train/{rung}",
                      lambda r=rung: [audit_train_cell(r)]))
    for gc in ("bf16", "int8"):
        specs.append((f"train/fused+{gc}",
                      lambda g=gc: [audit_train_cell("fused", g)]))
    specs.append(("train/fused+overlap",
                  lambda: [audit_train_cell("fused", overlap=True)]))
    specs.append(("mpmd", audit_mpmd_cells))
    specs.append(("serve", audit_serve_cells))
    specs.append(("long-context", audit_long_context_cells))
    specs.append(("fleet", audit_fleet_cell))
    specs.append(("publish", audit_publish_cells))
    specs.append(("moe", audit_moe_cells))
    specs.append(("diloco", audit_diloco_cell))
    specs.append(("redistribute", audit_redistribute_cell))
    if only is not None:
        specs = [(n, t) for n, t in specs
                 if any(o in n for o in only)]
    return specs


def main(only=None, write=True) -> int:
    cells = []
    for name, thunk in build_cells(only):
        try:
            got = thunk()
            got = got if isinstance(got, list) else [got]
        except Exception as e:  # noqa: BLE001 — failed cell is a datum
            got = [{"program": name,
                    "error": f"{type(e).__name__}: {e}"}]
        for cell in got:
            cells.append(cell)
            print(f"[graph-audit] {cell.get('program')}: "
                  f"colls={cell.get('n_collectives')} "
                  f"findings={len(cell.get('findings', []))}"
                  + (f" ERROR {cell['error']}" if "error" in cell
                     else ""),
                  flush=True)

    n_findings = sum(len(c.get("findings", [])) for c in cells)
    n_errors = sum(1 for c in cells if "error" in c)
    out = {
        "note": ("per-program static audit (tpu_ddp/analysis/): "
                 "donation = donate_argnums vs the executable's "
                 "input_output_alias (unaliased donation = a full "
                 "copy every call); precision = f32-widened "
                 "collectives under a reduced wire + f64 creep; "
                 "fingerprint = (op, dtype, payload bytes, replica "
                 "groups) per logical collective in program order — "
                 "async -start/-done pairs count ONCE — with the same "
                 "config lowered twice required to fingerprint "
                 "identically (SPMD lockstep). All compiled-HLO "
                 "claims, backend-independent; this artifact is the "
                 "committed zero-findings baseline CI diffs against."),
        "n_programs": len(cells),
        "n_findings": n_findings,
        "n_errors": n_errors,
        "cells": cells,
    }
    if write:
        (REPO / "experiments" / "graph_audit.json").write_text(
            json.dumps(out, indent=1))
    if n_findings or n_errors:
        print(f"graph audit: {n_findings} finding(s), "
              f"{n_errors} error(s)")
        for c in cells:
            for f in c.get("findings", []):
                print(f"  - {c['program']}: {f}")
            if "error" in c:
                print(f"  - {c['program']}: {c['error']}")
        return 1
    print(f"graph audit: {len(cells)} programs clean "
          "(donation, precision, lockstep determinism)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
