"""Long-context serving programs: tiered-KV step variants and
context-parallel chunked prefill (DESIGN.md §27).

Two families of compiled programs live here, both variants of the
round-12 serving steps in tpu_ddp/serve/engine.py:

**Tiered steps** read a pool whose pages straddle two device tiers
(tpu_ddp/serve/kv_pool.py): hot pages in the exact cache dtype, cold
pages quantized by the cold-page codec (parallel/compress.py). The
block table splits into TWO physical tables — hot slots and cold
slots, zero where the block is not in that tier — and the attention
view is built per layer as ``where(is_hot, hot_gather,
dequant(cold_gather))``. A block resident in NEITHER tier (fresh, or
an idle slot's null entries) reads both null pages and contributes
zeros, which the causal mask in ``attend_cached`` already ignores.
Writes always target hot slots — the engine promotes each sequence's
frontier block before stepping — so the scatter math is the round-12
scatter with the hot table in place of the logical one.

Why sampling parity survives quantized cold pages: ``sample_token`` is
keyed on (seed, position) only — the RNG stream never depends on KV
bytes — and ``cached_len``/block-table bookkeeping is host-side
integer state that tiering does not touch. Dequantization error
perturbs LOGITS only; at temperature 0 the argmax is bit-stable under
perturbations smaller than the top-2 logit gap, and with the bf16
cold codec under a bf16 hot dtype the round trip is exactly lossless,
which is what the parity cells in scripts/long_context_sweep.py pin.

**Context-parallel chunked prefill** shards ONE chunk of a long
prompt over the ``sp`` mesh axis: each rank embeds and projects its
``C/sp`` slice, attends with ring attention (K/V chunks rotating via
ppermute, the online-softmax state seeded from a replicated paged-pool
view of the already-committed prefix — ring_attention's ``cache_k``
path) or Ulysses all-to-alls, then the chunk's K/V and logits
all-gather and land in the pool with the SAME scatter as the
single-rank prefill step — one compiled program per chunk, the same
shape the round-14 disagg ``KVEdge`` uses to adopt shipped blocks.
The outer signature matches ``_build_prefill_step`` exactly, so the
engine swaps it in without touching the chunk loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpu_ddp.models.decode import (attend_cached, block_finish,
                                   project_qkv, sample_token)
from tpu_ddp.parallel.compress import page_dequantize
from tpu_ddp.serve.kv_pool import PagedKVPool, gather_view, rows
from tpu_ddp.utils.profiling import (
    SERVE_DECODE_TIERED,
    SERVE_PREFILL_CP,
    SERVE_PREFILL_TIERED,
    program,
)


def _mixed_view(hot_buf, cold_buf, cold_scale, li, hot_tables,
                cold_tables):
    """Per-layer attention view over a two-tier pool: gather hot pages
    and dequantized cold pages by their slot tables and select per
    block. ``hot_tables``/``cold_tables`` (S, BPS) int32, slot 0 =
    not in that tier (both null pages are zeros, kept so by scrub).
    Returns (S, BPS, bs, KV*hd) in the hot dtype."""
    # One gather on each whole buffer, as kv_pool.gather_view: a layer
    # sliced out first (``buf[li]``) would be copied whole.
    hk = hot_buf[li, hot_tables]
    ck = page_dequantize(cold_buf[li, cold_tables],
                         cold_scale[li, cold_tables], hot_buf.dtype)
    is_hot = (hot_tables > 0)[..., None, None]
    return jnp.where(is_hot, hk, ck)


def tiered_decode_bank(model, block_size: int, blocks_per_seq: int,
                       params, hot_k, hot_v, cold_k, cold_v, cold_sk,
                       cold_sv, hot_tables, cold_tables, lengths,
                       last_tokens, temps, seeds):
    """The tiered twin of ``engine.decode_bank``: one token for every
    live slot, reading hot pages directly and cold pages through the
    dequant, writing (the new token's KV) to the frontier hot slot.
    Identical sampling, non-finite detection and bookkeeping — only
    the gather/scatter addressing differs."""
    S = hot_tables.shape[0]
    cd = model.compute_dtype
    x = params["embed"][last_tokens[:, None]].astype(cd)
    pos = lengths[:, None]
    bidx = jnp.take_along_axis(
        hot_tables, (lengths // block_size)[:, None], axis=1)[:, 0]
    off = lengths % block_size
    view = (S, blocks_per_seq * block_size, model.kv_heads,
            model.head_dim)
    for li, blk in enumerate(params["blocks"]):
        q, k, v = project_qkv(model, blk, x, pos)
        hot_k = hot_k.at[li, bidx, off].set(
            rows(k[:, 0]).astype(hot_k.dtype))
        hot_v = hot_v.at[li, bidx, off].set(
            rows(v[:, 0]).astype(hot_v.dtype))
        ck = _mixed_view(hot_k, cold_k, cold_sk, li, hot_tables,
                         cold_tables).reshape(view)
        cv = _mixed_view(hot_v, cold_v, cold_sv, li, hot_tables,
                         cold_tables).reshape(view)
        o = attend_cached(model, q, ck, cv, pos)
        x = block_finish(model, blk, x, o)
    logits = model.head_apply(params, x)[:, 0]
    toks, lps = jax.vmap(
        lambda lg, t, sd, p: sample_token(model, lg, t, sd, p))(
            logits, temps, seeds, lengths + 1)
    bad = ~(jnp.all(jnp.isfinite(logits), axis=-1) & jnp.isfinite(lps))
    return hot_k, hot_v, toks, lps, bad


@functools.lru_cache(maxsize=32)
def build_tiered_decode_step(model, block_size: int,
                             blocks_per_seq: int):
    """Jitted whole-bank tiered decode. Hot buffers are donated (they
    are the mutating state); cold buffers and scales are read-only —
    decode never writes a cold page."""

    @program(SERVE_DECODE_TIERED)
    def step(params, hot_k, hot_v, cold_k, cold_v, cold_sk, cold_sv,
             hot_tables, cold_tables, lengths, last_tokens, temps,
             seeds):
        return tiered_decode_bank(model, block_size, blocks_per_seq,
                                  params, hot_k, hot_v, cold_k, cold_v,
                                  cold_sk, cold_sv, hot_tables,
                                  cold_tables, lengths, last_tokens,
                                  temps, seeds)

    return jax.jit(step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=32)
def build_tiered_prefill_step(model, block_size: int,
                              blocks_per_seq: int):
    """Jitted one-slot tiered prefill chunk — ``_build_prefill_step``
    with the two-table addressing. The chunk's own target blocks are
    hot (the engine promotes them first); earlier chunks' pages may
    have gone cold and are read through the dequant."""

    @program(SERVE_PREFILL_TIERED)
    def step(params, hot_k, hot_v, cold_k, cold_v, cold_sk, cold_sv,
             hot_table, cold_table, tokens, start, prompt_len, temp,
             seed):
        cd = model.compute_dtype
        C = tokens.shape[1]
        p = start + jnp.arange(C)
        valid = p < prompt_len
        safe = jnp.clip(p // block_size, 0, blocks_per_seq - 1)
        blk_idx = jnp.where(valid, hot_table[safe],
                            PagedKVPool.NULL_BLOCK)
        off = p % block_size
        x = params["embed"][tokens].astype(cd)
        view = (1, blocks_per_seq * block_size, model.kv_heads,
                model.head_dim)
        ht = hot_table[None]
        ct = cold_table[None]
        for li, blkp in enumerate(params["blocks"]):
            q, k, v = project_qkv(model, blkp, x, p)
            hot_k = hot_k.at[li, blk_idx, off].set(
                rows(k[0]).astype(hot_k.dtype))
            hot_v = hot_v.at[li, blk_idx, off].set(
                rows(v[0]).astype(hot_v.dtype))
            ck = _mixed_view(hot_k, cold_k, cold_sk, li, ht,
                             ct).reshape(view)
            cv = _mixed_view(hot_v, cold_v, cold_sv, li, ht,
                             ct).reshape(view)
            o = attend_cached(model, q, ck, cv, p)
            x = block_finish(model, blkp, x, o)
        logits = model.head_apply(params, x)[0]
        last = jnp.clip(prompt_len - 1 - start, 0, C - 1)
        tok, lp = sample_token(model, logits[last], temp, seed,
                               prompt_len)
        return hot_k, hot_v, tok, lp

    return jax.jit(step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=16)
def build_cp_prefill_step(model, block_size: int, blocks_per_seq: int,
                          mesh, sp: int, mode: str):
    """Jitted context-parallel prefill chunk: same outer signature as
    ``_build_prefill_step`` (so the engine's chunk loop is unchanged),
    but inside the program the chunk is sharded over the ``sp`` mesh
    axis and attended with ring attention (``mode="ring"``: K/V
    chunks rotate, cache seeded from the pool view) or Ulysses
    all-to-alls (``mode="ulysses"``: heads scatter, the cache slice
    rides each rank's head group). The chunk's K/V and logits then
    all-gather so the pool scatter and the boundary sample are
    replicated — identical math to the single-rank step."""
    from jax.sharding import PartitionSpec as P

    if mode == "ulysses":
        from tpu_ddp.parallel.ulysses import ulysses_attention
    else:
        from tpu_ddp.parallel.ring_attention import ring_attention
    cache_len = blocks_per_seq * block_size

    def body(params, pool_k, pool_v, table, tokens, start):
        cd = model.compute_dtype
        lc = tokens.shape[1]                     # C / sp local slice
        r = lax.axis_index("sp")
        p = start + r * lc + jnp.arange(lc)
        cache_valid = jnp.arange(cache_len) < start
        x = params["embed"][tokens].astype(cd)   # (1, lc, dm)
        ks, vs = [], []
        for li, blkp in enumerate(params["blocks"]):
            q, k, v = project_qkv(model, blkp, x, p)
            ck = gather_view(pool_k, li, table[None], model).astype(cd)
            cv = gather_view(pool_v, li, table[None], model).astype(cd)
            if mode == "ulysses":
                o = ulysses_attention(q, k, v, "sp", sp, causal=True,
                                      q_offset=start, cache_k=ck,
                                      cache_v=cv,
                                      cache_valid=cache_valid)
            else:
                o = ring_attention(q, k, v, "sp", sp, causal=True,
                                   q_offset=start, cache_k=ck,
                                   cache_v=cv, cache_valid=cache_valid)
            x = block_finish(model, blkp, x, o)
            ks.append(k)
            vs.append(v)
        logits = model.head_apply(params, x)[0]  # (lc, V)
        kc = lax.all_gather(jnp.stack(ks), "sp", axis=2, tiled=True)
        vc = lax.all_gather(jnp.stack(vs), "sp", axis=2, tiled=True)
        lg = lax.all_gather(logits, "sp", axis=0, tiled=True)
        return kc[:, 0], vc[:, 0], lg            # (L, C, KV, hd), (C, V)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(None, "sp"), P()),
        out_specs=(P(), P(), P()), check_vma=False)

    @program(SERVE_PREFILL_CP)
    def step(params, pool_k, pool_v, table, tokens, start, prompt_len,
             temp, seed):
        C = tokens.shape[1]
        kc, vc, lg = sharded(params, pool_k, pool_v, table, tokens,
                             start)
        p = start + jnp.arange(C)
        valid = p < prompt_len
        safe = jnp.clip(p // block_size, 0, blocks_per_seq - 1)
        blk_idx = jnp.where(valid, table[safe], PagedKVPool.NULL_BLOCK)
        off = p % block_size
        pool_k = pool_k.at[:, blk_idx, off].set(
            rows(kc).astype(pool_k.dtype))
        pool_v = pool_v.at[:, blk_idx, off].set(
            rows(vc).astype(pool_v.dtype))
        last = jnp.clip(prompt_len - 1 - start, 0, C - 1)
        tok, lp = sample_token(model, lg[last], temp, seed, prompt_len)
        return pool_k, pool_v, tok, lp

    return jax.jit(step, donate_argnums=(1, 2))
