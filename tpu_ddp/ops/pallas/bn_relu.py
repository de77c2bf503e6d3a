"""Fused BatchNorm(batch-statistics) + ReLU — Pallas forward and backward.

The reference's conv block is conv -> BatchNorm2d(track_running_stats=False)
-> ReLU (reference part1/model.py:18-25); with batch-only statistics the
BN+ReLU pair is a pure function of the current activation, which makes it
an ideal fusion target: one reduction pass (per-channel sum / sum-of-
squares) and one normalize+ReLU pass, each streaming the activation
through VMEM exactly once. The backward pass is the classic BN gradient

    dx = (scale * inv / R) * (R*gy - sum(gy) - x_hat * sum(gy * x_hat))

with the ReLU mask folded into ``gy``, again as one reduction pass + one
elementwise pass, wired up through ``jax.custom_vjp`` (Pallas kernels are
not auto-differentiable).

Layout: the NHWC activation is viewed as (R, C) with R = N*H*W rows.
Lane alignment without copies: when C divides 128 (e.g. VGG's first
64-channel layer), k = 128/C consecutive rows are FOLDED side-by-side into
a (R/k, 128) view — a free row-major reshape, no padding materialization;
per-channel vectors are tiled k times for the kernels and the k row-group
partial sums are combined afterwards. Only when C neither divides nor is a
multiple of 128 does the code fall back to zero-padding the channel axis.
Rows are chunked over a 1-D grid (grid steps are sequential on TPU, so
per-channel accumulators live in a (1, 128·m) output block shared by all
steps).

Measured verdict (TPU v5e, VGG-11 train step): XLA's own conv+BN+ReLU
fusion BEATS this kernel — 25.3 ms vs 66.0 ms per step at batch 2048
(8.1 vs 11.1 ms at 256) — because XLA fuses the normalize+ReLU into the
surrounding convolution epilogues while a custom kernel forces the
activation through VMEM as a separate pass. The kernel stays as an
opt-in (``TPU_DDP_PALLAS_BN=1``) reference implementation and a Pallas
pattern exemplar; the default path is the right one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 1024

BN_EPS = 1e-5  # torch BatchNorm2d default; callers pass the model's eps


# ---- layout: fold / pad to the 128-lane boundary ------------------------

def _layout(r, c):
    """Return (k, c_pad): fold factor and channel zero-pad width."""
    if c % _LANES == 0:
        return 1, 0
    if _LANES % c == 0 and r % (_LANES // c) == 0:
        return _LANES // c, 0
    return 1, -(-c // _LANES) * _LANES - c


def _fold_rows(x2d, k, c_pad):
    if k > 1:
        r, c = x2d.shape
        return x2d.reshape(r // k, c * k)  # free row-major view
    if c_pad:
        return jnp.pad(x2d, ((0, 0), (0, c_pad)))
    return x2d


def _fold_chan(v_1c, k, c_pad):
    """(1, C) channel vector -> (1, lane-width) for the kernels."""
    if k > 1:
        return jnp.tile(v_1c, (1, k))
    if c_pad:
        return jnp.pad(v_1c, ((0, 0), (0, c_pad)))
    return v_1c


def _combine_chan(s_folded, k, c):
    """(1, lane-width) kernel accumulator -> (1, C) per-channel totals."""
    if k > 1:
        return jnp.sum(s_folded.reshape(k, c), axis=0, keepdims=True)
    return s_folded[:, :c]


def _row_blocking(r):
    """Block rows (multiple of 8 sublanes) and the zero-pad to fill the
    last grid step. For the model's power-of-two activation shapes the pad
    is zero and ``jnp.pad`` is a no-op."""
    br = min(_BLOCK_ROWS, -(-r // 8) * 8)
    r_pad = -(-r // br) * br - r
    return br, r_pad


def _pad_rows(x, r_pad):
    return jnp.pad(x, ((0, r_pad), (0, 0))) if r_pad else x


def _row_spec(block_rows, lanes):
    return pl.BlockSpec((block_rows, lanes), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _chan_spec(lanes):
    return pl.BlockSpec((1, lanes), lambda i: (0, 0),
                        memory_space=pltpu.VMEM)


# ---- forward ------------------------------------------------------------

def _stats_kernel(x_ref, sum_ref, sumsq_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

    xb = x_ref[:]
    sum_ref[:] += jnp.sum(xb, axis=0, keepdims=True)
    sumsq_ref[:] += jnp.sum(xb * xb, axis=0, keepdims=True)


def _norm_relu_kernel(x_ref, mean_ref, inv_ref, scale_ref, bias_ref, y_ref):
    y = (x_ref[:] - mean_ref[:]) * (inv_ref[:] * scale_ref[:]) + bias_ref[:]
    y_ref[:] = jnp.maximum(y, 0.0)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _bn_relu_fwd_impl(x2d, scale, bias, *, eps, interpret):
    r, c = x2d.shape
    k, c_pad = _layout(r, c)
    xf = _fold_rows(x2d, k, c_pad)
    rf, lanes = xf.shape
    br, r_pad = _row_blocking(rf)
    xf = _pad_rows(xf, r_pad)
    grid = ((rf + r_pad) // br,)
    chan = jax.ShapeDtypeStruct((1, lanes), jnp.float32)

    s, ss = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[_row_spec(br, lanes)],
        out_specs=(_chan_spec(lanes), _chan_spec(lanes)),
        out_shape=(chan, chan),
        name="bn_relu_stats",
        interpret=interpret,
    )(xf)
    mean = _combine_chan(s, k, c) / r                      # (1, C)
    var = jnp.maximum(_combine_chan(ss, k, c) / r - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)                         # (1, C)

    y = pl.pallas_call(
        _norm_relu_kernel,
        grid=grid,
        in_specs=[_row_spec(br, lanes)] + [_chan_spec(lanes)] * 4,
        out_specs=_row_spec(br, lanes),
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        name="bn_relu_apply",
        interpret=interpret,
    )(xf, _fold_chan(mean, k, c_pad), _fold_chan(inv, k, c_pad),
      _fold_chan(scale.reshape(1, c), k, c_pad),
      _fold_chan(bias.reshape(1, c), k, c_pad))
    if r_pad:
        y = y[:rf]
    y = y.reshape(r, c) if k > 1 else y[:, :c]
    return y, mean, inv


# ---- backward -----------------------------------------------------------

def _bwd_stats_kernel(x_ref, g_ref, mean_ref, inv_ref, scale_ref, bias_ref,
                      dbias_ref, dscale_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dbias_ref[:] = jnp.zeros_like(dbias_ref)
        dscale_ref[:] = jnp.zeros_like(dscale_ref)

    x_hat = (x_ref[:] - mean_ref[:]) * inv_ref[:]
    y = x_hat * scale_ref[:] + bias_ref[:]
    gy = jnp.where(y > 0, g_ref[:], 0.0)
    dbias_ref[:] += jnp.sum(gy, axis=0, keepdims=True)
    dscale_ref[:] += jnp.sum(gy * x_hat, axis=0, keepdims=True)


def _bwd_dx_kernel(x_ref, g_ref, mean_ref, inv_ref, scale_ref, bias_ref,
                   dbias_ref, dscale_ref, dx_ref, *, count):
    x_hat = (x_ref[:] - mean_ref[:]) * inv_ref[:]
    y = x_hat * scale_ref[:] + bias_ref[:]
    gy = jnp.where(y > 0, g_ref[:], 0.0)
    dx_ref[:] = (scale_ref[:] * inv_ref[:] * (1.0 / count)) * (
        count * gy - dbias_ref[:] - x_hat * dscale_ref[:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bn_relu_bwd_impl(x2d, g2d, mean, inv, scale, bias, *, interpret):
    r, c = x2d.shape
    k, c_pad = _layout(r, c)
    br, r_pad = _row_blocking(r // k)
    xf = _pad_rows(_fold_rows(x2d, k, c_pad), r_pad)
    gf = _pad_rows(_fold_rows(g2d, k, c_pad), r_pad)
    rf, lanes = xf.shape
    grid = (rf // br,)
    chan = jax.ShapeDtypeStruct((1, lanes), jnp.float32)
    mean_f = _fold_chan(mean, k, c_pad)
    inv_f = _fold_chan(inv, k, c_pad)
    scale_f = _fold_chan(scale.reshape(1, c), k, c_pad)
    bias_f = _fold_chan(bias.reshape(1, c), k, c_pad)

    db_f, ds_f = pl.pallas_call(
        _bwd_stats_kernel,
        grid=grid,
        in_specs=[_row_spec(br, lanes)] * 2 + [_chan_spec(lanes)] * 4,
        out_specs=(_chan_spec(lanes), _chan_spec(lanes)),
        out_shape=(chan, chan),
        name="bn_relu_bwd_reduce",
        interpret=interpret,
    )(xf, gf, mean_f, inv_f, scale_f, bias_f)
    dbias = _combine_chan(db_f, k, c)                      # (1, C)
    dscale = _combine_chan(ds_f, k, c)

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, count=float(r)),
        grid=grid,
        in_specs=[_row_spec(br, lanes)] * 2 + [_chan_spec(lanes)] * 6,
        out_specs=_row_spec(br, lanes),
        out_shape=jax.ShapeDtypeStruct(xf.shape, jnp.float32),
        name="bn_relu_bwd_dx",
        interpret=interpret,
    )(xf, gf, mean_f, inv_f, scale_f, bias_f,
      _fold_chan(dbias, k, c_pad), _fold_chan(dscale, k, c_pad))
    if r_pad:
        dx = dx[:rf - r_pad]
    dx = dx.reshape(r, c) if k > 1 else dx[:, :c]
    return dx, dscale[0], dbias[0]


# ---- public op with custom VJP -----------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_relu(x, scale, bias, eps=BN_EPS):
    """``relu(batch_norm(x))`` over (..., C) using current-batch statistics.

    Drop-in fused replacement for ``batch_norm`` + ``maximum(·, 0)`` in
    tpu_ddp/models/vgg.py (the ``track_running_stats=False`` semantic of
    reference part1/model.py:24). Differentiable w.r.t. ``x``, ``scale``
    and ``bias``. Computes in float32 regardless of input dtype.
    """
    y, _, _ = _fwd(x, scale, bias, eps)
    return y


def _interpret():
    from tpu_ddp.ops.pallas import interpret_mode
    return interpret_mode()


def _fwd(x, scale, bias, eps):
    shape = x.shape
    x2d = x.astype(jnp.float32).reshape(-1, shape[-1])
    y2d, mean, inv = _bn_relu_fwd_impl(
        x2d, scale.astype(jnp.float32), bias.astype(jnp.float32),
        eps=float(eps), interpret=_interpret())
    return y2d.reshape(shape).astype(x.dtype), mean, inv


def _bn_relu_fwd(x, scale, bias, eps):
    y, mean, inv = _fwd(x, scale, bias, eps)
    return y, (x, mean, inv, scale, bias)


def _bn_relu_bwd(eps, residuals, g):
    x, mean, inv, scale, bias = residuals
    shape = x.shape
    x2d = x.astype(jnp.float32).reshape(-1, shape[-1])
    g2d = g.astype(jnp.float32).reshape(-1, shape[-1])
    dx2d, dscale, dbias = _bn_relu_bwd_impl(
        x2d, g2d, mean, inv, scale.astype(jnp.float32),
        bias.astype(jnp.float32), interpret=_interpret())
    return (dx2d.reshape(shape).astype(x.dtype),
            dscale.astype(scale.dtype), dbias.astype(bias.dtype))


batch_norm_relu.defvjp(_bn_relu_fwd, _bn_relu_bwd)
