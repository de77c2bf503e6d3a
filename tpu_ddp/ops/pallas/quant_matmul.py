"""Weight-only int8 matmul: ``x @ (q * s)`` without materializing fp
weights — the quantized-decode compute kernel (ops/quant.py).

Decode matmuls are weight-bandwidth-bound: activations are a few rows
(the live batch, or batch x (k+1) under speculation) while the weight
panel is the whole projection. The win is therefore byte traffic on
``q``: int8 tiles stream HBM->VMEM at 4x fewer bytes than f32, convert
to the MXU input dtype on the VMEM side of the wall, and the
per-output-column scale ``s`` fuses into the accumulator epilogue —
one kernel, zero fp-weight HBM traffic:

    acc(f32) = dot(x_tile, int8->f32(q_tile))   # MXU, f32 accumulate
    out      = acc * s_tile                     # epilogue, per column

Grid is (M tiles, N tiles) with the full K panel resident per program:
decode-shaped problems have small M and K = d_model, so a (bm, K)
activation block plus a (K, bn) weight block sit comfortably in VMEM
(K=8192 at bn=256 is 2 MB of int8). Inputs are zero-padded to lane
multiples by the wrapper and sliced back — zero K-padding contributes
exact zeros to the accumulator, zero N-padding is sliced away.

Runs compiled on TPU, interpreted elsewhere (tests force the host
platform); the XLA reference path in :func:`tpu_ddp.ops.quant.qdot`
computes the same contraction for CPU serving.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128      # TPU lane width: last-dim tile multiple
_BLOCK_M = 128    # activation rows per program
_BLOCK_N = 256    # output columns per program


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = q_ref[...].astype(jnp.float32)
    acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _qmm(x2d, q, s2d, *, interpret):
    m, k = x2d.shape
    n = q.shape[1]
    bm = min(_BLOCK_M, m)
    bn = min(_BLOCK_N, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((k, bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        name="quant_matmul",
        interpret=interpret,
    )(x2d, q, s2d)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def int8_matmul(x, q, s, *, interpret: bool | None = None):
    """``x @ (q.astype(f32) * s)`` in f32, weights read as int8.

    ``x``: (..., K) activations; ``q``: (K, N) int8; ``s``: (N,) f32
    per-output-column scales. Returns (..., N) f32. Leading axes are
    flattened into rows for the kernel and restored after — the
    decode call sites pass (B, L, K).
    """
    if interpret is None:
        from tpu_ddp.ops.pallas import interpret_mode
        interpret = interpret_mode()
    k, n = q.shape
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    # Lane-align every dim: zero K-padding adds exact zeros to the
    # accumulator, M/N padding is sliced away below. int8 sublane tile
    # is 32, so K pads to the f32 lane width (covers both operands).
    x2d = _pad_to(_pad_to(x2d, 1, _LANES), 0, 8)
    qp = _pad_to(_pad_to(q, 0, _LANES), 1, _LANES)
    sp = _pad_to(s.reshape(1, n), 1, _LANES)
    out = _qmm(x2d, qp, sp, interpret=bool(interpret))
    return out[:m, :n].reshape(*lead, n)
