"""Masked attention of a sparse prefill chunk — Pallas TPU kernel.

A sparse-attention layer of a prefill chunk (tpu_ddp/serve/engine.py
``sparse_chunk``) attends each of the chunk's ``C`` queries over the
slot's gathered K/V view, ``T`` positions, keeping only the positions
the indexer selected for that query: a (C, T) mask. The jnp form
(``decode.attend_masked`` over parts of the chunk) presents the MXU a
few queries at a time and computes the scores of the whole table two or
three times a part (max, sum, weighted sum); this kernel computes them
once, in one flash-style pass (Dao et al., arXiv:2205.14135) with the
mask applied to each tile of scores.

TPU mapping:
- grid ``(query blocks, key blocks)``, key blocks innermost: the running
  max, sum and float32 accumulator of every row of a query block live in
  VMEM scratch across the sweep, and the output is written on its last
  step. Every key block of the table is visited: the work is fixed by
  the shapes, not by where the chunk sits or what its mask keeps;
- a step holds one ``(bk, KV*hd)`` block of K and of V, all K/V heads
  side by side (the gathered view reshaped, not transposed), and loops
  over the heads: head ``h`` is the lane-aligned columns
  ``[h*hd, (h+1)*hd)``. K/V are read once a query block;
- a tile's rows are the ``G`` query heads of one K/V head times the
  ``bq`` queries of the block, ``(KV, G, C, hd)`` head-major, so the MXU
  sees ``G * bq`` rows against each key block and the mask's ``(bq,
  bk)`` block is the same for the ``G`` heads;
- scores in float32 (bf16 operands, float32 accumulation), masked to
  ``-1e30``; the weights rounded to the value dtype for the weighted
  sum, with float32 accumulation, which is what XLA:TPU's default
  precision does with ``attend_masked``'s float32 product;
- the running max starts at half of ``-1e30``, above every masked
  score: ``exp(masked - max)`` is then 0 from the first block on, so a
  block in which a row keeps nothing leaves that row's max, sum and
  accumulator exactly as they were. A row must keep at least one
  position (every query keeps itself or an earlier one), or its output
  is 0 / 0.

Tiles follow from the shapes alone (:func:`_tiles`); :func:`supports`
says, from shapes and dtypes, where the kernel takes the chunk, and
``sparse_chunk`` keeps the jnp form elsewhere. Read on a v5e at the
sparse cell's chunk (512 queries, 32 heads over 4 K/V heads of 128,
36,864 positions, bf16): a layer takes 2.50 ms alone and 2.26 ms inside
the prefill program, where the jnp form took 5.70 ms alone; tiles of 512
keys took 4.82 ms, of 2,048 the same as of 1,024.

Runs compiled on TPU and in interpreter mode elsewhere; tested against
``decode.attend_masked`` in tests/test_sparse_prefill_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_START = _NEG_INF / 2         # the running max before any kept score
_LANES = 128
_MASK_ROWS = 32               # rows of one int8 tile: a query block's unit
_ROWS = 1024                  # rows of a tile: G query heads x bq queries
_KEYS = 1024                  # keys of a tile, at most
_VMEM_BYTES = 64 << 20        # of the v5e's 128 MiB
_VMEM_SPARE = 8 << 20         # the compiler's own, beside the buffers
_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def _tiles(C: int, T: int, G: int) -> tuple[int, int]:
    """``(bq, bk)``: the most queries, a multiple of 32 dividing ``C``,
    whose ``G`` heads make at most ``_ROWS`` rows (at least 32), and the
    most keys, a multiple of 128 dividing ``T``, up to ``_KEYS``. 0 where
    no such block exists."""
    bq = max((b for b in range(_MASK_ROWS, C + 1, _MASK_ROWS)
              if C % b == 0 and (b * G <= _ROWS or b == _MASK_ROWS)),
             default=0)
    bk = max((b for b in range(_LANES, min(T, _KEYS) + 1, _LANES)
              if T % b == 0), default=0)
    return bq, bk


def _vmem_bytes(bq: int, bk: int, H: int, KV: int, hd: int,
                itemsize: int) -> int:
    """q and output blocks, K and V blocks and the mask's, each twice;
    the scratch; and a head's float32 scores three times over."""
    rows = bq * H // KV
    blocks = 2 * (2 * bq * H * hd * itemsize + 2 * bk * KV * hd * itemsize
                  + bq * bk)
    scratch = KV * rows * (2 * _LANES + hd) * 4
    return blocks + scratch + 3 * rows * bk * 4


def supports(C: int, T: int, H: int, KV: int, hd: int, dtype) -> bool:
    """Does the kernel take ``C`` queries of ``H`` heads against ``T``
    positions of ``KV`` heads of ``hd``, all in ``dtype``? Shapes and
    dtypes only: bf16 or float32, heads whole lane tiles, whole query and
    key blocks, and the buffers within the on-chip budget."""
    if jnp.dtype(dtype) not in _DTYPES or hd % _LANES or H % KV:
        return False
    bq, bk = _tiles(C, T, H // KV)
    return bool(bq and bk) and _vmem_bytes(
        bq, bk, H, KV, hd, jnp.dtype(dtype).itemsize) \
        + _VMEM_SPARE <= _VMEM_BYTES


def _kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale):
    kv, g, bq, hd = q_ref.shape
    bk = k_ref.shape[0]
    rows = g * bq

    @pl.when(pl.program_id(1) == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _START)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    keep = keep_ref[...] != 0                               # (bq, bk)

    def head(h, carry):
        cols = pl.ds(pl.multiple_of(h * hd, _LANES), hd)
        v = v_ref[:, cols]
        s = lax.dot_general(q_ref[h].reshape(rows, hd), k_ref[:, cols],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep[None], s.reshape(g, bq, bk),
                      _NEG_INF).reshape(rows, bk)
        m_prev = m_sc[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[h] = jnp.broadcast_to(
            alpha * l_sc[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            (rows, _LANES))
        acc_sc[h] = acc_sc[h] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[h] = jnp.broadcast_to(m_new, (rows, _LANES))
        return carry

    lax.fori_loop(0, kv, head, 0)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_sc[...] / l_sc[:, :, :1]).reshape(
            kv, g, bq, hd).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bq", "bk", "interpret"))
def _impl(q4, kv_k, kv_v, keep, *, scale, bq, bk, interpret):
    KV, G, C, hd = q4.shape
    T = kv_k.shape[0]
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(C // bq, T // bk),
        in_specs=[pl.BlockSpec((KV, G, bq, hd), lambda i, j: (0, 0, i, 0)),
                  pl.BlockSpec((bk, KV * hd), lambda i, j: (j, 0)),
                  pl.BlockSpec((bk, KV * hd), lambda i, j: (j, 0)),
                  pl.BlockSpec((bq, bk), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((KV, G, bq, hd), lambda i, j: (0, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        scratch_shapes=[pltpu.VMEM((KV, G * bq, _LANES), jnp.float32),
                        pltpu.VMEM((KV, G * bq, _LANES), jnp.float32),
                        pltpu.VMEM((KV, G * bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(
                bq, bk, G * KV, KV, hd, jnp.dtype(q4.dtype).itemsize)
            + _VMEM_SPARE),
        name="sparse_prefill_attn",
        interpret=interpret,
    )(q4, kv_k, kv_v, keep)


def sparse_prefill_attention(q, ck, cv, keep, *, scale: float):
    """``q`` (C, H, hd) over ``ck`` / ``cv`` (T, KV, hd), query ``i``
    reading position ``s`` where ``keep[i, s]`` (C, T, bool or int8):
    (C, H, hd) in ``q``'s dtype, query head ``h`` reading K/V head ``h //
    (H / KV)``. Every row of ``keep`` must hold a true. Raises for shapes
    ``supports`` refuses. Compiled on the TPU, interpreted elsewhere."""
    from tpu_ddp.ops.pallas import interpret_mode
    C, H, hd = q.shape
    T, KV = ck.shape[:2]
    if ck.shape != (T, KV, hd) or cv.shape != ck.shape \
            or keep.shape != (C, T) or not q.dtype == ck.dtype == cv.dtype:
        raise ValueError(
            f"q (C, H, hd), ck / cv (T, KV, hd) of q's dtype, keep (C, T); "
            f"got {q.shape} {q.dtype}, {ck.shape} {ck.dtype}, {cv.shape} "
            f"{cv.dtype}, {keep.shape}")
    if not supports(C, T, H, KV, hd, q.dtype):
        raise ValueError(
            f"sparse_prefill_attention does not take C={C}, T={T}, H={H}, "
            f"KV={KV}, hd={hd}, {q.dtype} (see supports())")
    bq, bk = _tiles(C, T, H // KV)
    q4 = q.reshape(C, KV, H // KV, hd).transpose(1, 2, 0, 3)
    out = _impl(q4, ck.reshape(T, KV * hd), cv.reshape(T, KV * hd),
                keep.astype(jnp.int8), scale=float(scale), bq=bq, bk=bk,
                interpret=interpret_mode())
    return out.transpose(2, 0, 1, 3).reshape(C, H, hd)
