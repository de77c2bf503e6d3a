"""The yardstick: chip peaks, and the operations and bytes that a step
needs, computed from shapes alone.

Copied from ``tpu_ddp/utils/flops.py`` (``_PEAKS``, ``_HBM_GBPS``,
``vgg_fwd_flops``, ``transformer_fwd_flops``, ``train_flops``) so that a
later change to the program cannot move it. Conventions: a multiply-add
is two operations; only matrix multiplications and convolutions count;
training costs three forward passes (backward makes two products for
each forward one); recomputed operations never count; attention is
counted over the full L x L square, as PaLM's appendix B does.

Peaks are the published ones of one chip (Google Cloud documentation,
"TPU v5e": 197 TFLOP/s in bf16, 819 GB/s of HBM). A device kind that is
not in the table is an error, never a default.
"""

from __future__ import annotations

# device_kind (as jax.Device reports it) -> (bf16 TFLOP/s, HBM GB/s)
PEAKS = {
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
}


def peak(device_kind: str) -> tuple[float, float]:
    """(FLOP/s in bf16, HBM bytes/s) of one chip of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; add it to lib/shapes.py with "
                       "its source")
    tflops, gbps = PEAKS[device_kind]
    return tflops * 1e12, gbps * 1e9


def vgg_train_flops_per_image(plan, image_size: int = 32,
                              num_classes: int = 10,
                              in_channels: int = 3) -> int:
    """Forward and backward operations of one image through a VGG made
    of 3x3 SAME convolutions, 2x2 pools (``"M"``) and one linear head."""
    h = w = image_size
    c_in = in_channels
    fwd = 0
    for width in plan:
        if width == "M":
            h //= 2
            w //= 2
            continue
        fwd += 2 * 9 * c_in * width * h * w
        c_in = width
    fwd += 2 * c_in * num_classes
    return 3 * fwd


def lm_matmul_params(cfg: dict) -> int:
    """Parameters of a dense decoder that sit in a matrix product (each
    costs two operations a token): projections, MLP and output head."""
    dm, dff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = dm // h
    per_layer = dm * (h * hd + 2 * kvh * hd) + h * hd * dm + 2 * dm * dff
    return cfg["num_hidden_layers"] * per_layer + dm * cfg["vocab_size"]


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward operations per trained token at ``seq_len``."""
    attn = 4 * cfg["hidden_size"] * seq_len * cfg["num_hidden_layers"]
    return 3 * (2 * lm_matmul_params(cfg) + attn)


def lm_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes of weights one decode step has to read: every matrix once
    (the embedding table is indexed, not read)."""
    return lm_matmul_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of K and V that one cached position holds over all layers."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * hd * bytes_per_value)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """Bytes a whole-batch decode step must read: the bf16 weights once
    and the live K/V of the occupied slots."""
    return lm_weight_bytes(cfg) + live_context_tokens * kv_bytes_per_token(cfg)
