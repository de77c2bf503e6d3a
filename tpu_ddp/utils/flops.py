"""Analytic FLOP accounting + chip peak detection — the MFU story.

The reference's report judges performance as time/iteration on known
hardware (CS744__Assignment_2.pdf §3, Table 1); on TPU the honest analogue
is MFU: achieved model FLOP/s divided by the chip's peak. This module
provides the three ingredients the bench needs:

- ``*_fwd_flops``: analytic forward FLOPs per step for each model family
  (matmul/conv terms only, multiply+add = 2 FLOPs; BN/LN/softmax/elementwise
  are bandwidth- not FLOP-bound and are excluded, the standard MFU
  convention). Training FLOPs = ``TRAIN_FLOPS_MULT`` x forward (backward
  does the two grad matmuls per forward matmul). Attention is counted at
  the full L^2 term (PaLM appendix-B convention — causal masking halves the
  work the chip does but not the "model FLOPs" denominator).
- ``xla_flops``: the compiled program's own FLOP count from XLA's cost
  analysis — includes everything (backward, optimizer, remat recompute), so
  it is the *hardware* FLOP count; recorded alongside as a cross-check.
- ``peak_tflops``: bf16 dense per-chip peak by ``device_kind``, overridable
  with ``TPU_DDP_PEAK_TFLOPS`` for kinds not in the table.
"""

from __future__ import annotations

import os

import numpy as np

# Backward pass ~= 2x forward (one matmul each for dL/dx and dL/dW per
# forward matmul); optimizer FLOPs are negligible against the matmuls.
TRAIN_FLOPS_MULT = 3

# bf16 dense peak TFLOP/s PER CHIP, keyed by substrings of
# jax.Device.device_kind (checked in order; first match wins). Public
# numbers: v2 180/board(4 chips), v3 123/chip, v4 275, v4i 138,
# v5e 197, v5p 459, v6e (Trillium) 918.
_PEAKS = (
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v5litepod", 197.0),
    ("v5", 459.0),
    ("v4 lite", 138.0),
    ("v4i", 138.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
)


def peak_tflops(device) -> tuple[float | None, str]:
    """(bf16 peak TFLOP/s for ``device``, source string).

    ``TPU_DDP_PEAK_TFLOPS`` overrides (for chips not in the table); a
    non-TPU platform or unknown kind returns (None, reason) — the bench
    then reports achieved FLOP/s but a null MFU rather than a wrong one.
    """
    env = os.environ.get("TPU_DDP_PEAK_TFLOPS")
    if env:
        try:
            return float(env), "env:TPU_DDP_PEAK_TFLOPS"
        except ValueError:
            return None, f"unparseable TPU_DDP_PEAK_TFLOPS={env!r}"
    return _by_device_kind(device, _PEAKS, "peak")


def _by_device_kind(device, table, what: str) -> tuple[float | None, str]:
    """First ``table`` entry whose key is in the device's kind, with its
    source; (None, reason) off-TPU or for a kind not in the table."""
    if device.platform != "tpu":
        return None, (f"non-TPU platform {device.platform!r}: "
                      f"no {what} table")
    kind = device.device_kind.lower()
    for sub, value in table:
        if sub in kind:
            return value, f"device_kind {device.device_kind!r}"
    return None, f"unknown device_kind {device.device_kind!r}"


def vgg_fwd_flops(cfg, image_size: int, batch: int,
                  num_classes: int = 10, in_channels: int = 3) -> int:
    """Forward FLOPs for one VGG step (models/vgg.py channel plans)."""
    h = w = image_size
    c_in = in_channels
    per_image = 0
    for width in cfg:
        if width == "M":
            h //= 2
            w //= 2
            continue
        per_image += 2 * 9 * c_in * width * h * w  # 3x3 SAME conv
        c_in = width
    per_image += 2 * c_in * num_classes  # 512 -> classes head
    return per_image * batch


def resnet_fwd_flops(stage_blocks, image_size: int, batch: int,
                     num_classes: int = 1000, in_channels: int = 3,
                     small_inputs: bool = False) -> int:
    """Forward FLOPs for one bottleneck-ResNet step, mirroring the shape
    walk of models/resnet.py:apply (stem, 4 stages, head)."""
    stage_widths = (64, 128, 256, 512)
    h = image_size // (1 if small_inputs else 2)
    stem_hw = 3 if small_inputs else 7
    per_image = 2 * stem_hw * stem_hw * in_channels * 64 * h * h
    if not small_inputs:
        h //= 2  # stem max-pool
    c_in = 64
    for si, n_blocks in enumerate(stage_blocks):
        width = stage_widths[si]
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            h_out = h // stride
            per_image += 2 * c_in * width * h * h            # conv1 1x1
            per_image += 2 * 9 * width * width * h_out * h_out  # conv2 3x3
            per_image += 2 * width * width * 4 * h_out * h_out  # conv3 1x1
            if bi == 0 and c_in != width * 4:
                per_image += 2 * c_in * width * 4 * h_out * h_out  # proj
            c_in = width * 4
            h = h_out
    per_image += 2 * c_in * num_classes
    return per_image * batch


def transformer_fwd_flops(model, batch: int, seq_len: int) -> int:
    """Forward FLOPs for one decoder-LM step (models/transformer.py).

    2 x (matmul params) per token + the attention score/value matmuls at
    4*d_model*L per token per layer (full-L convention; GQA changes K/V
    projection size, not the score matmuls). MoE models count ACTIVE
    expert params (top_k experts per token).
    """
    dm, dff = model.d_model, model.d_ff
    h, kvh, hd = model.num_heads, model.kv_heads, model.head_dim
    per_layer = dm * (h * hd + 2 * kvh * hd)   # wqkv (fused or split)
    per_layer += h * hd * dm                   # wo
    mlp = 2 * dm * dff                         # w1 + w2
    if model.moe_experts:
        mlp *= max(model.moe_top_k, 1)         # active experts per token
        per_layer += dm * model.moe_experts    # router
    per_layer += mlp
    matmul_params = model.num_layers * per_layer + dm * model.vocab_size
    tokens = batch * seq_len
    attn = 4 * dm * seq_len * model.num_layers  # QK^T + AV per token
    return tokens * (2 * matmul_params + attn)


def vit_fwd_flops(model, batch: int) -> int:
    """Forward FLOPs for one ViT classifier step (models/vit.py): patch
    embed + encoder blocks (full-L² attention convention) + GAP head."""
    n = model.num_patches
    dm, dff = model.d_model, model.d_ff
    patch_in = model.patch_size ** 2 * model.in_channels
    per_image = 2 * n * patch_in * dm                    # patch embed GEMM
    per_layer = 2 * n * (4 * dm * dm + 2 * dm * dff)     # qkv+o, mlp
    per_layer += 4 * n * n * dm                          # QK^T + AV
    per_image += model.num_layers * per_layer
    per_image += 2 * dm * model.num_classes
    return per_image * batch


def train_flops(fwd_flops: int) -> int:
    return TRAIN_FLOPS_MULT * fwd_flops


def xla_flops(jitted_fn, *args) -> float | None:
    """FLOPs of the compiled program per XLA's cost analysis, or None if
    the backend doesn't report them. This counts what the hardware
    executes (incl. remat recompute), not the analytic model FLOPs."""
    try:
        analysis = jitted_fn.lower(*args).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = analysis.get("flops")
        if flops is None or not np.isfinite(flops) or flops <= 0:
            return None
        return float(flops)
    except Exception:
        return None


# Public HBM bandwidth GB/s per chip, keyed like _PEAKS: v2 700/board,
# v3 900, v4 1228, v5e 819, v5p 2765, v6e (Trillium) 1640.
_HBM_GBPS = (
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v5litepod", 819.0),
    ("v5", 2765.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def device_hbm_gbps(device) -> tuple[float | None, str]:
    """(HBM bandwidth GB/s for ``device``, source string).

    The :func:`peak_tflops` contract: a non-TPU platform or a kind not in
    the table returns (None, reason), so bandwidth utilization is
    reported as null rather than against another chip's peak.
    ``TPU_DDP_HBM_GBPS`` overrides (for chips not in the table); a value
    that does not parse raises ``ValueError``."""
    env = os.environ.get("TPU_DDP_HBM_GBPS")
    if env:
        try:
            return float(env), "env:TPU_DDP_HBM_GBPS"
        except ValueError:
            raise ValueError(
                f"TPU_DDP_HBM_GBPS={env!r} is not a number") from None
    return _by_device_kind(device, _HBM_GBPS, "bandwidth")


def mfu_fields(flops_per_step: float | None, step_seconds: float,
               device, xla_flops_per_step: float | None = None) -> dict:
    """The bench JSON's MFU block: achieved TFLOP/s, peak, MFU."""
    peak, peak_src = peak_tflops(device)
    out = {
        "flops_per_step": flops_per_step,
        "flops_source": "analytic" if flops_per_step is not None else None,
        "xla_flops_per_step": xla_flops_per_step,
        "peak_tflops_bf16": peak,
        "peak_source": peak_src,
        "achieved_tflops": None,
        "mfu": None,
    }
    if flops_per_step is None and xla_flops_per_step is not None:
        flops_per_step = xla_flops_per_step
        out["flops_per_step"] = flops_per_step
        out["flops_source"] = "xla_cost_analysis"
    if flops_per_step and step_seconds > 0:
        achieved = flops_per_step / step_seconds / 1e12
        out["achieved_tflops"] = round(achieved, 3)
        if peak:
            out["mfu"] = round(achieved / peak, 4)
    return out
