"""Validate the conv-family roofline traffic terms against the COMPILED
program (round-4 verdict item 1b).

The rooflines (scripts/vgg_roofline.py, scripts/resnet_roofline.py)
PREDICT per-step HBM traffic from a 6-passes-per-conv-output model.
This script compiles the REAL jitted train step (the exact program
bench.py times) and reads XLA's own cost analysis — ``flops`` and
``bytes accessed`` — off the compiled executable, recording
model-vs-compiler deltas per family and batch size:

- ``bytes accessed`` is XLA's post-fusion estimate of memory traffic
  for the whole step (params + activations + optimizer state), so the
  roofline's ACTIVATION traffic must come in at or under it; the gap
  is the params/optimizer/im2col traffic the activation-only model
  does not charge.
- ``flops`` cross-checks the analytic 3x-forward count the MFU block
  already uses (utils/flops.py, xla_flops).

Run ON THE BENCH CHIP (the TPU's fusion decisions are the ones that
matter); the JSON records the platform so a CPU run is never mistaken
for the real validation. Writes experiments/conv_traffic_validation.json.

    python scripts/conv_traffic_validate.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import numpy as np  # noqa: E402


def _cost(compiled) -> dict:
    """flops / bytes-accessed from a compiled executable's cost
    analysis (key names vary slightly across jax versions)."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001 — record, don't die
        return {"cost_analysis_error": f"{type(e).__name__}: {e}"}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    out = {}
    for k, v in dict(ca).items():
        lk = k.lower()
        if lk == "flops":
            out["xla_flops"] = float(v)
        elif lk in ("bytes accessed", "bytes accessed{}"):
            out["xla_bytes_accessed"] = float(v)
    if "xla_bytes_accessed" not in out:
        # Operand-level keys ("bytes accessed0{}", ...) exist on some
        # versions without the total; record what we saw for debugging.
        out["cost_analysis_keys"] = sorted(dict(ca).keys())[:20]
    return out


def _time_step(trainer, state, staged, iters: int = 8,
               windows: int = 3) -> float:
    """Median chained-window avg s/step — bench.py's gated protocol
    (reused, not re-implemented: this number backs the committed
    achieved-bandwidth claims, so it gets the same spread gate as
    every bench number)."""
    import bench
    med, _, _ = bench._chained_avg_s(trainer.train_step, state,
                                     [staged], iters, windows)
    return med


def measure(config: str, batch: int) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    cfg = TrainConfig.preset(config)
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      use_pallas_bn=cfg.pallas_bn,
                      compute_dtype=jnp.dtype(cfg.compute_dtype))
    trainer = Trainer(model, cfg, strategy="fused",
                      mesh=make_mesh(jax.devices()[:1]))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    side = cfg.image_size
    x = rng.integers(0, 256, size=(batch, side, side, 3)).astype(np.uint8)
    y = rng.integers(0, cfg.num_classes, size=batch).astype(np.int32)
    staged = trainer.put_batch(x, y)
    lowered = trainer._train_step.lower(state.params, state.opt_state,
                                        *staged)
    compiled = lowered.compile()
    out = {"config": config, "batch": batch,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind}
    out.update(_cost(compiled))

    # The roofline's predicted ACTIVATION traffic + analytic flops.
    if config == "vgg11_cifar10":
        from scripts.vgg_roofline import layers as vgg_layers
        rows = vgg_layers(batch, image_size=side,
                          num_classes=cfg.num_classes)
        out["model_activation_bytes"] = int(sum(t for _, _, t, _, _
                                                in rows))
        out["model_train_flops"] = float(sum(3.0 * f for _, f, _, _, _
                                             in rows))
    else:
        from scripts.resnet_roofline import (ACT_BYTES, TRAFFIC_FACTOR,
                                             layers as res_layers)
        rows = res_layers(batch, image_size=side,
                          num_classes=cfg.num_classes)
        out["model_activation_bytes"] = int(sum(
            TRAFFIC_FACTOR * ACT_BYTES * e for _, _, e, _, _ in rows))
        out["model_train_flops"] = float(sum(3.0 * f for _, f, _, _, _
                                             in rows))
    n_params = sum(int(p.size) for p in jax.tree.leaves(state.params))
    # Param-side traffic the activation-only roofline does not charge:
    # read f32 params fwd+bwd, write f32 grads, read+write f32 momentum
    # and params in the update ~ 7 * 4 * P bytes.
    out["n_params"] = n_params
    out["param_side_bytes_estimate"] = 7 * 4 * n_params
    if "xla_bytes_accessed" in out:
        out["model_over_xla_bytes"] = round(
            out["model_activation_bytes"] / out["xla_bytes_accessed"], 4)
        out["model_plus_params_over_xla"] = round(
            (out["model_activation_bytes"]
             + out["param_side_bytes_estimate"])
            / out["xla_bytes_accessed"], 4)
    if "xla_flops" in out and out.get("model_train_flops"):
        out["model_over_xla_flops"] = round(
            out["model_train_flops"] / out["xla_flops"], 4)

    # Measured step time -> achieved bandwidth against XLA's OWN bytes
    # (the term the analytic roofline cannot see: how much of the 819
    # GB/s the compiled schedule actually sustains).
    from tpu_ddp.utils import flops as F
    if jax.devices()[0].platform == "tpu":
        step_s = _time_step(trainer, state, staged)
        out["measured_step_s"] = round(step_s, 6)
        peak, _ = F.peak_tflops(jax.devices()[0])
        bw_gbps, _ = F.device_hbm_gbps(jax.devices()[0])
        out["hbm_peak_gbps"] = bw_gbps
        if "xla_bytes_accessed" in out:
            xb = out["xla_bytes_accessed"]
            out["achieved_hbm_gbps"] = round(xb / step_s / 1e9, 1)
            if bw_gbps:
                bw = bw_gbps * 1e9
                out["bytes_bound_step_s"] = round(xb / bw, 6)
                out["achieved_hbm_frac"] = round(xb / bw / step_s, 4)
        if peak:
            out["flops_bound_step_s"] = round(
                out["model_train_flops"] / (peak * 1e12), 6)
            out["measured_mfu_analytic"] = round(
                out["model_train_flops"] / (peak * 1e12 * step_s), 4)
    return out


def bn_stats_cost(batch: int) -> dict:
    """What do batch statistics COST in XLA's actual schedule?

    Compiles the VGG-11 forward+loss twice — once as-is, once with
    ``batch_norm`` monkeypatched to a stats-free affine (same elementwise
    shape, no mean/var reductions) — and diffs the cost analysis. If the
    bytes delta is ~one conv-output read per layer, a fused conv-epilogue
    stats kernel has that much traffic to win; if it is ~0, XLA already
    fuses the stats reads into the conv epilogues and the round-4 §7
    hypothesis (a Pallas stats-epilogue lever) has no traffic to claim.
    Semantics note: the affine variant is NOT BatchNorm — it exists only
    to expose the reductions' marginal cost in the compiled schedule.
    """
    import jax
    import jax.numpy as jnp

    from tpu_ddp.models import get_model
    from tpu_ddp.models import vgg as vgg_mod
    from tpu_ddp.ops.loss import softmax_cross_entropy

    model = get_model("VGG11", compute_dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10, size=batch), jnp.int32)

    def loss(p, x, y):
        logits = model.apply(p, x)
        return jnp.mean(softmax_cross_entropy(logits, y))

    def compile_cost(fn, train: bool):
        f = jax.value_and_grad(fn) if train else fn
        return _cost(jax.jit(f).lower(params, x, y).compile())

    out = {"batch": batch}
    real_bn = vgg_mod.batch_norm
    try:
        out["fwd_bn"] = compile_cost(loss, train=False)
        out["train_bn"] = compile_cost(loss, train=True)
        vgg_mod.batch_norm = (
            lambda xx, scale, bias, eps=vgg_mod.BN_EPS:
            (xx.astype(jnp.float32) * scale + bias).astype(xx.dtype))
        out["fwd_affine"] = compile_cost(loss, train=False)
        out["train_affine"] = compile_cost(loss, train=True)
    finally:
        vgg_mod.batch_norm = real_bn
    for phase in ("fwd", "train"):
        a = out.get(f"{phase}_bn", {}).get("xla_bytes_accessed")
        b = out.get(f"{phase}_affine", {}).get("xla_bytes_accessed")
        if a and b:
            out[f"{phase}_stats_bytes_delta"] = a - b
            out[f"{phase}_stats_bytes_delta_pct"] = round(
                100.0 * (a - b) / a, 1)
    return out


def main() -> int:
    cells = []
    for config, batches in (("vgg11_cifar10", (1024, 4096, 16384)),
                            ("resnet50_imagenet", (128, 512))):
        for b in batches:
            try:
                cell = measure(config, b)
            except Exception as e:  # noqa: BLE001 — record, don't die
                cell = {"config": config, "batch": b,
                        "error": f"{type(e).__name__}: {e}"}
            cells.append(cell)
            print(f"[traffic-validate] {config} batch {b}: "
                  f"{json.dumps({k: v for k, v in cell.items() if k not in ('config', 'batch')})}",
                  flush=True)
    bn_cells = []
    for b in (1024, 4096):
        try:
            bn_cells.append(bn_stats_cost(b))
        except Exception as e:  # noqa: BLE001 — record, don't die
            bn_cells.append({"batch": b,
                             "error": f"{type(e).__name__}: {e}"})
        print(f"[bn-stats-cost] batch {b}: "
              f"{json.dumps(bn_cells[-1], default=str)}", flush=True)
    out = {
        "note": ("xla_bytes_accessed = XLA cost analysis over the "
                 "compiled train step (post-fusion, whole step); "
                 "model_activation_bytes = the roofline's 6-pass "
                 "activation-traffic prediction; the remainder is "
                 "params/grads/optimizer traffic "
                 "(param_side_bytes_estimate ~ 7*4*P) and any im2col/"
                 "transpose materialization the model does not charge. "
                 "achieved_hbm_frac = xla_bytes / (819 GB/s * measured "
                 "step) — the sustained-bandwidth fraction, the term "
                 "the analytic roofline cannot see"),
        "bn_stats_note": ("bn_stats cells diff the compiled VGG "
                          "forward/train against a stats-free affine "
                          "variant: the bytes delta is what batch "
                          "statistics actually cost in XLA's schedule "
                          "— the traffic a fused conv-epilogue stats "
                          "kernel could claim (round-4 verdict 1c)"),
        "cells": cells,
        "bn_stats": bn_cells,
    }
    (REPO / "experiments" / "conv_traffic_validation.json").write_text(
        json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
