"""Measure what each activation-remat / residual-precision policy
(tpu_ddp/memory/) actually does to the compiled train step.

The policies trade recompute FLOPs for saved-residual bytes — the right
direction on a 819 GB/s : 197 TFLOP/s chip ONLY if the compiled program
agrees. This sweep compiles the REAL jitted train step per (model,
batch, remat, act_dtype) cell — the exact program bench.py times — and
records, per cell:

- ``xla_flops`` / ``xla_bytes_accessed`` from the compiled executable's
  cost analysis (conv_traffic_validate.py's reader): the recompute tax
  and the traffic claim, from the compiler itself. Note bytes-accessed
  counts every operand touch, so recompute can RAISE it even while the
  live-activation footprint falls — both directions are the honest
  record, which is why the next number exists.
- ``temp_bytes`` from ``compiled.memory_analysis()`` (zero2_memory.py's
  reader): XLA's buffer-assignment peak for temporaries — the
  live-residual footprint the remat policy exists to shrink, and a
  platform-independent claim (buffer assignment, not timing).
- measured step time + achieved-HBM fraction, ON TPU ONLY (CPU timing
  says nothing about the bandwidth wall; those fields are null on a CPU
  run and the recorded ``platform`` keeps the provenance honest —
  same contract as conv_traffic_validation.json).

Grid: the bench families at their committed batch sizes, plus the
LM-small plain-batch-256 cell that motivated the subsystem (no remat,
its activation working set failed to compile on the v5e — EXPERIMENTS
§8; under remat=blocks it must compile).

Writes experiments/remat_sweep.json.

    python scripts/remat_sweep.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import os  # noqa: E402

# Measured fact (jaxlib 0.4.37, CPU backend): XLA's CSE can fold a
# small checkpoint region's recomputation back into the saved values
# across the optimization barriers — the per-BLOCK conv cells (VGG
# unit / ResNet bottleneck) compile to the byte-identical executable as
# remat=none on CPU, while the larger regions (conv stages, transformer
# blocks, dots) survive and show real deltas. The default sweep keeps
# the STANDARD pipeline — the program users actually run is the one
# measured, and a folded cell reading delta=0 is the honest datum for
# this backend. TPU_DDP_SWEEP_NO_CSE=1 opts into disabling the cse HLO
# pass (before jax initializes) to expose the policy structure on
# backends that fold it; cells record ``xla_cse_disabled`` so the two
# kinds of artifact can never be confused.
_CSE_DISABLED = False
if os.environ.get("TPU_DDP_SWEEP_NO_CSE") == "1" \
        and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_disable_hlo_passes=cse")
    _CSE_DISABLED = True

import numpy as np  # noqa: E402

from scripts.conv_traffic_validate import _cost  # noqa: E402


def _memory(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {"temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "argument_bytes": int(getattr(ma,
                                              "argument_size_in_bytes", 0))}
    except Exception as e:  # noqa: BLE001 — record, don't die
        return {"memory_analysis_error": f"{type(e).__name__}: {e}"}


def _timing(trainer, state, staged, compiled_cost: dict) -> dict:
    """Measured step time + achieved-HBM fraction — TPU only (a CPU
    step time says nothing about the 819 GB/s wall)."""
    import jax

    import bench
    from tpu_ddp.utils import flops as F

    if jax.devices()[0].platform != "tpu":
        return {"measured_step_s": None, "achieved_hbm_frac": None}
    step_s, _, _ = bench._chained_avg_s(trainer.train_step, state,
                                        [staged], 8, 3)
    out = {"measured_step_s": round(step_s, 6)}
    bw_gbps, _ = F.device_hbm_gbps(jax.devices()[0])
    xb = compiled_cost.get("xla_bytes_accessed")
    if xb:
        out["achieved_hbm_gbps"] = round(xb / step_s / 1e9, 1)
        out["achieved_hbm_frac"] = (
            round(xb / (bw_gbps * 1e9) / step_s, 4) if bw_gbps else None)
    return out


def measure_conv_cell(config: str, batch: int, remat: str,
                      act_dtype: str = "compute",
                      with_time: bool = True) -> dict:
    """One (preset, batch, policy) cell for the image families."""
    import jax
    import jax.numpy as jnp

    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    cfg = TrainConfig.preset(config)
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      use_pallas_bn=cfg.pallas_bn,
                      compute_dtype=jnp.dtype(cfg.compute_dtype),
                      remat=remat, act_dtype=act_dtype)
    trainer = Trainer(model, cfg, strategy="fused",
                      mesh=make_mesh(jax.devices()[:1]))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    side = cfg.image_size
    x = rng.integers(0, 256,
                     size=(batch, side, side, 3)).astype(np.uint8)
    y = rng.integers(0, cfg.num_classes, size=batch).astype(np.int32)
    staged = trainer.put_batch(x, y)
    compiled = trainer._train_step.lower(state.params, state.opt_state,
                                         *staged).compile()
    out = {"config": config, "batch": batch, "remat": remat,
           "act_dtype": act_dtype,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "xla_cse_disabled": _CSE_DISABLED}
    out.update(_cost(compiled))
    out.update(_memory(compiled))
    if with_time:
        out.update(_timing(trainer, state, staged, out))
    return out


def measure_lm_cell(batch: int, remat: str, act_dtype: str = "compute",
                    seq_len: int = 2048,
                    model_name: str = "TransformerLM-small",
                    with_time: bool = True) -> dict:
    """One LM cell. Compiled ABSTRACTLY (jax.eval_shape params ->
    AOT lower/compile): the point of the batch-256 cells is whether the
    program COMPILES and what its buffers cost, which must be
    measurable even on hosts that cannot hold the no-remat working set.
    Timing (TPU only) runs on the concrete path for the cells that fit.
    """
    import jax

    from tpu_ddp.models import make_transformer
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer, make_lm_batch

    model = make_transformer(model_name, max_seq_len=seq_len,
                             remat=remat, act_dtype=act_dtype)
    trainer = LMTrainer(model, make_mesh(jax.devices()[:1]))
    out = {"config": model_name, "batch": batch, "seq_len": seq_len,
           "remat": remat, "act_dtype": act_dtype,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind,
           "xla_cse_disabled": _CSE_DISABLED}

    import types

    import jax.numpy as jnp

    abstract_params = jax.eval_shape(model.init, jax.random.key(0))
    abstract_opt = jax.eval_shape(trainer.optimizer.init,
                                  abstract_params)
    xb = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    extra = jax.eval_shape(
        lambda: trainer._extra_args(types.SimpleNamespace(step=0)))
    compiled = trainer._train_step.lower(
        abstract_params, abstract_opt, xb, xb, *extra).compile()
    out.update(_cost(compiled))
    out.update(_memory(compiled))
    if with_time and jax.devices()[0].platform == "tpu":
        state = trainer.init_state()
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, model.vocab_size,
                              size=(batch, seq_len + 1))
        staged = trainer.put_batch(*make_lm_batch(tokens))
        out.update(_timing(trainer, state, staged, out))
    else:
        out.update({"measured_step_s": None,
                    "achieved_hbm_frac": None})
    return out


# The grid: per family, the no-remat baseline plus each policy that is
# NOT a duplicate cell for that family (tune/space.py violations():
# dots==conv_stages on convs, conv_stages degrades to blocks on attn).
GRID = [
    ("conv", dict(config="vgg11_cifar10", batch=256), "none", "compute"),
    ("conv", dict(config="vgg11_cifar10", batch=256), "blocks", "compute"),
    ("conv", dict(config="vgg11_cifar10", batch=256),
     "conv_stages", "compute"),
    ("conv", dict(config="resnet50_imagenet", batch=512),
     "none", "compute"),
    ("conv", dict(config="resnet50_imagenet", batch=512),
     "blocks", "compute"),
    ("conv", dict(config="resnet50_imagenet", batch=512),
     "conv_stages", "compute"),
    # Residual-precision axis on the acceptance cell: the policy pair
    # (blocks, f32) pins the act_dtype cost in the same table.
    ("conv", dict(config="resnet50_imagenet", batch=512),
     "blocks", "f32"),
    ("conv", dict(config="vit_cifar10", batch=256), "none", "compute"),
    ("conv", dict(config="vit_cifar10", batch=256), "blocks", "compute"),
    ("conv", dict(config="vit_cifar10", batch=256), "dots", "compute"),
    # The motivating LM cells: batch 32 compiled without remat on the
    # v5e (EXPERIMENTS §8); plain batch 256 did not. The none cell at
    # 256 is expected to fail on-chip — a recorded error IS the datum.
    ("lm", dict(batch=32), "none", "compute"),
    ("lm", dict(batch=256), "none", "compute"),
    ("lm", dict(batch=256), "blocks", "compute"),
    ("lm", dict(batch=256), "dots", "compute"),
]


def main() -> int:
    cells = []
    for kind, kw, remat, act in GRID:
        fn = measure_conv_cell if kind == "conv" else measure_lm_cell
        try:
            cell = fn(remat=remat, act_dtype=act, **kw)
        except Exception as e:  # noqa: BLE001 — a failed cell is a datum
            cell = {**kw, "remat": remat, "act_dtype": act,
                    "error": f"{type(e).__name__}: {e}"}
        cells.append(cell)
        print(f"[remat-sweep] {kw} remat={remat} act={act}: "
              f"{json.dumps({k: v for k, v in cell.items() if k not in kw}, default=str)}",
              flush=True)

    out = {
        "note": ("per-cell: xla_flops/xla_bytes_accessed = XLA cost "
                 "analysis of the compiled train step (recompute can "
                 "RAISE bytes-accessed while shrinking live residuals "
                 "— both recorded); temp_bytes = XLA buffer-assignment "
                 "peak for temporaries (the footprint remat shrinks; "
                 "platform-independent); measured_step_s/"
                 "achieved_hbm_frac TPU-only, null on CPU runs. "
                 "Duplicate policy cells per family are omitted "
                 "(tune/space.py violations() encodes why). A cell "
                 "whose numbers EQUAL its none baseline is a real "
                 "datum: this backend's CSE folded that region's "
                 "recompute back across the optimization barriers "
                 "(observed for the per-block conv cells on CPU; the "
                 "larger stage/transformer regions survive). "
                 "TPU_DDP_SWEEP_NO_CSE=1 reruns with the cse pass off "
                 "(cells then record xla_cse_disabled=true) to expose "
                 "the policy structure on such backends — those "
                 "numbers are relative comparisons, never "
                 "standard-pipeline traffic claims"),
        "cells": cells,
    }
    (REPO / "experiments" / "remat_sweep.json").write_text(
        json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
