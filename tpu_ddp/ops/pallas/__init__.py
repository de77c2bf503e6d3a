"""Pallas TPU kernels for the framework's hot ops.

The reference's compute kernels all live in ATen C++ (SURVEY.md §2 row N3);
the TPU-native replacement is mostly XLA-emitted HLO, but the ops where a
hand-written kernel pays — single-pass fused elementwise chains that XLA
would otherwise split across HBM round-trips — are implemented here with
Pallas:

- :mod:`sgd`      — fused SGD momentum+weight-decay parameter update
                    (one read + one write per buffer instead of the
                    multi-op elementwise chain).
- :mod:`bn_relu`  — fused BatchNorm(batch-stats)+ReLU forward/backward
                    with a custom VJP.
- :mod:`flash_attention` — flash attention forward/backward: O(L·D) HBM
                    traffic instead of the O(L²) score matrix.
- :mod:`quant_matmul` — weight-only int8 matmul with the dequant scale
                    fused into the epilogue (quantized decode compute,
                    ops/quant.py).
- :mod:`paged_attention` — decode attention over the paged K/V pool,
                    read where it lies (serve/engine.py).
- :mod:`ssm_state_step` — the one-token Mamba-2 state step over the
                    serving state pool: the state read once, written
                    once, and ``y`` summed out of the tile held.
- :mod:`grouped_matmul` — the dropless expert layer's grouped product
                    over sorted rows: each held expert's matrix
                    streamed once past rows resident on chip.
- :mod:`sparse_prefill_attention` — a sparse-attention layer's prefill
                    chunk over the gathered K/V with the indexer's
                    selection as the mask: the scores computed once, in
                    one flash-style pass.

Every kernel runs compiled on TPU and falls back to interpreter mode on
CPU (tests force the host platform, conftest.py), selected automatically.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True when Pallas must run interpreted (no TPU backend)."""
    return jax.default_backend() != "tpu"


from tpu_ddp.ops.pallas.sgd import fused_sgd_step  # noqa: E402
from tpu_ddp.ops.pallas.bn_relu import batch_norm_relu  # noqa: E402
from tpu_ddp.ops.pallas.flash_attention import flash_attention  # noqa: E402
from tpu_ddp.ops.pallas.quant_matmul import int8_matmul  # noqa: E402

__all__ = ["interpret_mode", "fused_sgd_step", "batch_norm_relu",
           "flash_attention", "int8_matmul"]
