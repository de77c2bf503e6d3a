"""Paged decode attention — Pallas TPU kernel over the serving K/V pool.

The decode step's attention (tpu_ddp/serve/engine.py ``decode_bank``):
ONE query token per slot against that slot's cached context, which lives
in the block-paged pool (tpu_ddp/serve/kv_pool.py). The jnp path gathers
a layer's pool through the block tables into a contiguous
``(slots, max_seq_len, KV, hd)`` view and contracts over all of it, so a
step moves ``max_seq_len x slots`` of K/V whatever the requests hold.
This kernel reads the pool WHERE IT LIES: the whole K and V pools stay in
HBM, the kernel picks the layer and walks each slot's block table with
its own DMAs, up to that slot's live length, with the online-softmax
recurrence in float32 (Dao et al., arXiv:2205.14135; the paging after
Kwon et al., "PagedAttention", arXiv:2309.06180 — written from the
algorithms, with jax's ``pallas.ops.tpu.paged_attention`` read as the
model for the DMA pattern). Bytes and time follow the live context.

TPU mapping:
- pool layout ``(L, N, block_size, KV*hd)``: a page is one contiguous
  ``(block_size, KV*hd)`` tile run, K/V head ``h`` is the lane-aligned
  columns ``[h*hd, (h+1)*hd)``. ``pool.at[layer, page]`` is what one DMA
  moves, so nothing layer-sized or pool-sized is ever sliced out;
- one invocation walks all slots: the layer index, block tables and
  lengths ride in SMEM (scalar prefetch), ``q`` and the output are whole
  in VMEM, pages stream through a double buffer of ``_PAGES`` pages, and
  the next chunk — the next slot's first one included — is in flight
  while the current one is contracted, so no slot pays a DMA's latency
  alone;
- pages past ``ceil(length / block_size)`` are never fetched; positions
  past the length (the last page's tail, buffer pages not fetched) get
  an exact zero weight and their V rows are zeroed, so neither stale
  nor non-finite values there can reach the output;
- GQA as ``decode.attend_cached`` does it: the G query heads of a K/V
  head contract against that head's columns, no expansion.

A slot of length 0 (an idle slot seen alone) costs one page and returns
zeros. A pool dtype other than ``q``'s (``serve_cache_dtype``) is cast
after the load, in VMEM: K to the promoted dtype for the score matmul, V
to float32 — what ``attend_cached`` does to the gathered view.

:func:`supports` is the one predicate (shapes and dtypes only) that says
whether Mosaic's tiling takes a configuration; the serve step asks it
when the program is built and keeps the gather body otherwise.

Runs compiled on TPU and in interpreter mode elsewhere. Exactness vs
``attend_cached`` over the gathered view is tested in
tests/test_paged_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e30
_PAGES = 8        # pages per compute chunk (one DMA each for K and V)

# Sublane tile of a pool dtype Mosaic can load: rows per (rows, 128) tile.
_SUBLANES = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}


def supports(head_dim: int, block_size: int, pool_dtype, q_dtype) -> bool:
    """True when the kernel takes this configuration: K/V heads are
    whole lane tiles (``head_dim`` a multiple of 128), a page is whole
    sublane tiles of the pool's dtype, and pool and query dtypes are
    ones the kernel loads (float32, bfloat16)."""
    rows = _SUBLANES.get(jnp.dtype(pool_dtype))
    return (rows is not None and jnp.dtype(q_dtype) in _SUBLANES
            and head_dim % _LANES == 0 and block_size % rows == 0)


def _kernel(layer_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
            vbuf, sems, *, scale, bps):
    S, kvh, _, hd = q_ref.shape
    P, bs = kbuf.shape[1], kbuf.shape[2]
    T = P * bs
    layer = layer_ref[0]

    def pages_of(s):
        return jnp.maximum(pl.cdiv(len_ref[s], bs), 1)

    def page_copies(s, c, b, j):
        page = tab_ref[s * bps + c * P + j]
        return (pltpu.make_async_copy(k_hbm.at[layer, page], kbuf.at[b, j],
                                      sems.at[0, b]),
                pltpu.make_async_copy(v_hbm.at[layer, page], vbuf.at[b, j],
                                      sems.at[1, b]))

    def for_live_pages(s, c, b, act):
        live = pages_of(s) - c * P
        for j in range(P):
            @pl.when(j < live)
            def _():
                for cp in page_copies(s, c, b, j):
                    act(cp)

    def slot(s, b):
        n_chunks = pl.cdiv(pages_of(s), P)
        length = len_ref[s]

        def chunk(c, carry):
            b, m, l, acc = carry
            last = c == n_chunks - 1
            ns = jnp.where(last, s + 1, s)

            @pl.when(ns < S)
            def _():
                for_live_pages(jnp.minimum(ns, S - 1),
                               jnp.where(last, 0, c + 1), 1 - b,
                               lambda cp: cp.start())

            for_live_pages(s, c, b, lambda cp: cp.wait())
            live = c * T + lax.broadcasted_iota(jnp.int32, (1, T), 1) \
                < length                                      # (1, T)
            live_rows = c * T + lax.broadcasted_iota(
                jnp.int32, (T, 1), 0) < length                # (T, 1)
            ms, ls, accs = [], [], []
            for h in range(kvh):
                cols = slice(h * hd, (h + 1) * hd)
                q = q_ref[s, h]                               # (G, hd)
                k = kbuf[b, :, :, cols].reshape(T, hd)
                v = vbuf[b, :, :, cols].reshape(T, hd)
                cd = jnp.promote_types(q.dtype, k.dtype)
                sc = lax.dot_general(
                    q.astype(cd), k.astype(cd), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale   # (G, T)
                sc = jnp.where(live, sc, _NEG_INF)
                m_new = jnp.maximum(m[h], jnp.max(sc, axis=-1,
                                                  keepdims=True))
                alpha = jnp.exp(m[h] - m_new)
                p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
                v = jnp.where(live_rows, v.astype(jnp.float32), 0.0)
                ms.append(m_new)
                ls.append(alpha * l[h]
                          + jnp.sum(p, axis=-1, keepdims=True))
                # The weights stay float32 through the contraction: one
                # bf16 pass would round them to 8 bits, a second error
                # the size of the output's own rounding. With it the
                # output is the bf16 rounding of the exact result
                # (measured on the v5e, PERF.md PR 27).
                accs.append(alpha * acc[h] + jnp.dot(
                    p, v, precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32))
            return 1 - b, tuple(ms), tuple(ls), tuple(accs)

        G = q_ref.shape[2]
        init = (b,
                tuple(jnp.full((G, 1), _NEG_INF, jnp.float32)
                      for _ in range(kvh)),
                tuple(jnp.zeros((G, 1), jnp.float32) for _ in range(kvh)),
                tuple(jnp.zeros((G, hd), jnp.float32)
                      for _ in range(kvh)))
        b, _, l, acc = lax.fori_loop(0, n_chunks, chunk, init)
        for h in range(kvh):
            o_ref[s, h] = (acc[h] / jnp.maximum(l[h], 1e-30)
                           ).astype(o_ref.dtype)
        return b

    for_live_pages(0, 0, 0, lambda cp: cp.start())
    lax.fori_loop(0, S, slot, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def _paged_impl(layer, q4, pool_k, pool_v, tables, lengths, *, interpret,
                scale):
    kvh, hd = q4.shape[1], q4.shape[3]
    bs = pool_k.shape[2]
    whole = lambda *_: (0, 0, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bps=tables.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(q4.shape, whole,
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(q4.shape, whole,
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, _PAGES, bs, kvh * hd), pool_k.dtype),
                pltpu.VMEM((2, _PAGES, bs, kvh * hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        name="paged_decode_attn",
        interpret=interpret,
    )(layer, lengths.astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), q4, pool_k, pool_v)


def paged_decode_attention(q, pool_k, pool_v, tables, lengths, *,
                           layer: int, kv_heads: int,
                           scale: float | None = None,
                           interpret: bool | None = None):
    """Attention of one query token per slot over the paged pool.

    ``q``: (S, H, hd); ``pool_k`` / ``pool_v``: the WHOLE pools,
    ``(L, N, block_size, KV*hd)``; ``layer``: which layer's pages (an
    operand, not a constant of the kernel: the 30 calls of a 30-layer
    step are ONE traced, lowered and compiled kernel, which is what
    keeps the step's set-up time where it was); ``tables``: (S, BPS)
    int32 block ids; ``lengths``: (S,) int32, the positions each slot
    attends (``0..length-1``); ``scale``: what the scores are multiplied
    by (the model's ``attn_scale``; ``1/sqrt(hd)`` if not given).
    Returns (S, H, hd) in ``q``'s dtype. Query head ``h`` reads K/V head
    ``h // (H / KV)``, the grouping of ``attend_cached``."""
    if interpret is None:
        from tpu_ddp.ops.pallas import interpret_mode
        interpret = interpret_mode()
    S, H, hd = q.shape
    if pool_k.shape != pool_v.shape or pool_k.ndim != 4 \
            or pool_k.shape[3] != kv_heads * hd:
        raise ValueError(
            f"pools must both be (L, N, block_size, {kv_heads * hd}); got "
            f"{pool_k.shape} and {pool_v.shape}")
    if not supports(hd, pool_k.shape[2], pool_k.dtype, q.dtype):
        raise ValueError(
            f"paged_decode_attention does not take head_dim={hd}, "
            f"block_size={pool_k.shape[2]}, pool dtype {pool_k.dtype}, "
            f"query dtype {q.dtype} (see supports())")
    q4 = q.reshape(S, kv_heads, H // kv_heads, hd)
    out = _paged_impl(jnp.full((1,), layer, jnp.int32), q4, pool_k, pool_v,
                      tables, lengths, interpret=bool(interpret),
                      scale=1.0 / (hd ** 0.5) if scale is None
                      else float(scale))
    return out.reshape(S, H, hd)
