"""Speculative decoding: up to ``k+1`` tokens per engine step instead
of one (DESIGN.md §26).

The plain engine (``spec_k == 0``) emits one token per live slot per
decode program, dispatched a step ahead of its harvest (DESIGN.md
§19). Speculation multiplies tokens per program: classic
draft-then-verify, fused into ONE jitted program
(``build_spec_step``). The ``spec_draft`` knob picks the draft:
``"self-<j>"`` is an early exit over the target's first j blocks
sharing ln_f/head, ``"quant"`` a full-depth int8 twin — the natural
pairing with a quantized target, ops/quant.py. The draft proposes k
tokens by ``lax.scan``; the target then evaluates all k+1 columns
inside the same program and samples its own token at every position.
One dispatch and one host sync per step for up to k+1 tokens, where
the draft's shallow/int8 steps cost a fraction of the full-depth
steps they stand in for.

**The accept rule** (isolated in ``accept_length``):
the host emits the longest prefix of *target* samples whose inputs
the draft guessed right — column ``c`` is valid iff the draft's
proposal for position ``c`` equals the target's own sample at
``c-1``. What is emitted is always the target's sample stream
``t_0, t_1, ...``; the draft only decides how many of those samples
are computable this step. A wrong guess truncates the prefix; it
never substitutes a draft token, so no residual-distribution
correction is needed and every step emits at least one token
(``t_0`` is the token the non-speculative step would have produced).
Every position samples with the same stateless
``fold_in(seed, position)`` key the one-token step uses
(models/decode.py) — a draft sharing those keys makes the same
categorical draw whenever its logits are close, which is what buys
the acceptance rate at temperature > 0.

**KV rollback**: draft and verify both scatter K/V
into the paged pool at positions ``L..L+k`` (verify overwrites every
layer with target values BEFORE attending, so accepted positions end
bitwise correct regardless of the draft's arithmetic). On rejection
the tail positions beyond the new length hold garbage — harmless,
because the causal mask zeroes scores at positions > any query
*before* softmax — and the scheduler's ``trim_blocks`` frees whole
tail blocks back to the pool, so ``free + Σ allocated == total``
holds between steps with no new pool invariant. Writes past the
request's ``prompt + max_new`` budget are masked to the null block,
so speculation never allocates beyond the admission-time worst-case
reservation.

**Why the fused program does not claim bitwise parity with the
one-token step on CPU**: the verify columns are unrolled inside the
one program with per-column shapes identical to the one-token decode
bank, but XLA is free to re-tile or horizontally fuse across
columns — on the CPU backend this drifts individual logits by an ulp
relative to the standalone decode program, occasionally flipping a
categorical draw. (A W-wide batched verify drifts the same way via
gemm M-extent tiling, and ``lax.scan`` column bodies via loop-region
fusion; ``optimization_barrier`` does not prevent it.) Only samples
of the same compiled program are bitwise comparable, so the tests
hold the fused stream to its own invariants (budgets, the ledger
identity, pool accounting, version stamps: tests/test_speculative.py),
not to equality with the one-token stream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpu_ddp.models.decode import (
    attend_cached,
    block_finish,
    project_qkv,
    sample_token,
)
from tpu_ddp.serve.kv_pool import PagedKVPool, gather_view, rows
from tpu_ddp.utils.config import parse_spec_draft
from tpu_ddp.utils.profiling import SERVE_SPEC, program

__all__ = ["parse_spec_draft", "draft_bank", "verify_bank",
           "build_spec_step", "accept_length", "SPEC_DRAFTS"]

# Values of the spec_draft knob for callers that enumerate; the grammar
# is utils/config.py's parse_spec_draft.
SPEC_DRAFTS = ("self-1", "self-2", "quant")


def draft_bank(model, num_layers: int, block_size: int,
               blocks_per_seq: int, params, pool_k, pool_v, tables,
               lengths, last_tokens, temps, seeds, limits, k: int):
    """Autoregressive k-token draft over the first ``num_layers``
    blocks of ``params`` (the full stack for a "quant" draft) — a
    ``lax.scan`` of k one-token whole-bank steps sharing the target's
    paged pool. Each iteration feeds the previous token at position
    ``lengths + i``, writes its K/V (masked to the null block at or
    beyond ``limits``, the request's prompt+max_new budget), attends,
    and samples with the SAME ``fold_in(seed, position)`` key the
    target will use at that position — similar logits then make the
    same categorical draw, which is what buys the acceptance rate.
    Returns (pool_k, pool_v, proposals (S, k))."""
    cd = model.compute_dtype
    blocks = params["blocks"][:num_layers]

    def one(carry, i):
        pool_k, pool_v, tok = carry
        pos = lengths + i                                   # (S,)
        valid = pos < limits
        safe = jnp.clip(pos // block_size, 0, blocks_per_seq - 1)
        bidx = jnp.where(
            valid,
            jnp.take_along_axis(tables, safe[:, None], axis=1)[:, 0],
            PagedKVPool.NULL_BLOCK)
        off = pos % block_size
        x = params["embed"][tok[:, None]].astype(cd)        # (S, 1, dm)
        for li, blk in enumerate(blocks):
            q, kk, vv = project_qkv(model, blk, x, pos[:, None])
            pool_k = pool_k.at[li, bidx, off].set(
                rows(kk[:, 0]).astype(pool_k.dtype))
            pool_v = pool_v.at[li, bidx, off].set(
                rows(vv[:, 0]).astype(pool_v.dtype))
            ck = gather_view(pool_k, li, tables, model)
            cv = gather_view(pool_v, li, tables, model)
            o = attend_cached(model, q, ck, cv, pos[:, None])
            x = block_finish(model, blk, x, o)
        logits = model.head_apply(params, x)[:, 0]          # (S, V)
        nxt, _ = jax.vmap(
            lambda lg, t, sd, p: sample_token(model, lg, t, sd, p))(
                logits, temps, seeds, pos + 1)
        return (pool_k, pool_v, nxt), nxt

    (pool_k, pool_v, _), drafted = lax.scan(
        one, (pool_k, pool_v, last_tokens), jnp.arange(k))
    return pool_k, pool_v, jnp.transpose(drafted)           # (S, k)


def verify_bank(model, block_size: int, blocks_per_seq: int, params,
                pool_k, pool_v, tables, lengths, tok_mat, temps,
                seeds, limits):
    """The target's verification of ``tok_mat`` (S, W) — column 0 is
    each slot's pending token, columns 1..W-1 the draft's proposals —
    occupying absolute positions ``lengths..lengths+W-1``. The W
    columns are evaluated sequentially (unrolled) inside the one
    program, each column the one-token ``serve.engine.decode_bank``
    math at the same per-column shapes — the closest a fused program
    gets to the standalone decode step (see the module docstring for
    why cross-program bitwise parity still isn't guaranteed on CPU).
    Every column scatters its K/V into the pool —
    overwriting whatever the draft wrote there with target values —
    before attending, and positions at or beyond ``limits`` scatter
    to the null block. Samples the target's own token at every
    position with the stateless per-position keys; returns (pool_k,
    pool_v, tokens (S, W), logprobs (S, W), bad (S, W))."""
    W = tok_mat.shape[1]
    cd = model.compute_dtype

    def column(pool_k, pool_v, tok, c):
        pos = lengths + c
        valid = pos < limits
        safe = jnp.clip(pos // block_size, 0, blocks_per_seq - 1)
        bidx = jnp.where(
            valid,
            jnp.take_along_axis(tables, safe[:, None], axis=1)[:, 0],
            PagedKVPool.NULL_BLOCK)
        off = pos % block_size
        x = params["embed"][tok[:, None]].astype(cd)        # (S, 1, dm)
        for li, blk in enumerate(params["blocks"]):
            q, k, v = project_qkv(model, blk, x, pos[:, None])
            pool_k = pool_k.at[li, bidx, off].set(
                rows(k[:, 0]).astype(pool_k.dtype))
            pool_v = pool_v.at[li, bidx, off].set(
                rows(v[:, 0]).astype(pool_v.dtype))
            ck = gather_view(pool_k, li, tables, model)
            cv = gather_view(pool_v, li, tables, model)
            o = attend_cached(model, q, ck, cv, pos[:, None])
            x = block_finish(model, blk, x, o)
        logits = model.head_apply(params, x)[:, 0]          # (S, V)
        toks, lps = jax.vmap(
            lambda lg, t, sd, p: sample_token(model, lg, t, sd, p))(
                logits, temps, seeds, pos + 1)
        bad = ~(jnp.all(jnp.isfinite(logits), axis=-1)
                & jnp.isfinite(lps))
        return pool_k, pool_v, toks, lps, bad

    cols = []
    for c in range(W):
        pool_k, pool_v, toks, lps, bad = column(
            pool_k, pool_v, tok_mat[:, c], c)
        cols.append((toks, lps, bad))
    stack = lambda i: jnp.stack([col[i] for col in cols], axis=1)
    return pool_k, pool_v, stack(0), stack(1), stack(2)


# Memoized like the engine's decode/prefill builders: every engine
# sharing (model, geometry, k, draft depth) shares ONE compiled
# program. The draft tree's treedef (fp vs QuantizedWeight leaves) is
# part of jit's dispatch key, so "self-j" and "quant" drafts — and
# fp vs int8 targets — get distinct cache entries automatically.
@functools.lru_cache(maxsize=32)
def build_spec_step(model, block_size: int, blocks_per_seq: int,
                    k: int, draft_layers: int):
    """The fused speculative step: draft scan + verify as ONE jitted
    program — one dispatch, one host sync, up to k+1 tokens per slot.
    ``draft_layers`` is j for a self-draft, ``model.num_layers`` for
    a quantized full-depth draft (the draft family is otherwise
    carried entirely by the ``dparams`` tree)."""
    if not 1 <= draft_layers <= model.num_layers:
        raise ValueError(
            f"draft_layers must be in 1..{model.num_layers}, got "
            f"{draft_layers}")
    if k < 1:
        raise ValueError(f"spec_k must be >= 1 to speculate, got {k}")

    @program(SERVE_SPEC)
    def step(params, dparams, pool_k, pool_v, tables, lengths,
             last_tokens, temps, seeds, limits):
        pool_k, pool_v, drafted = draft_bank(
            model, draft_layers, block_size, blocks_per_seq, dparams,
            pool_k, pool_v, tables, lengths, last_tokens, temps,
            seeds, limits, k)
        tok_mat = jnp.concatenate([last_tokens[:, None], drafted],
                                  axis=1)                   # (S, k+1)
        pool_k, pool_v, toks, lps, bad = verify_bank(
            model, block_size, blocks_per_seq, params, pool_k, pool_v,
            tables, lengths, tok_mat, temps, seeds, limits)
        return pool_k, pool_v, drafted, toks, lps, bad

    return jax.jit(step, donate_argnums=(2, 3))


def accept_length(drafted, target, k: int) -> int:
    """The accept rule, isolated for unit testing: the number of
    proposals accepted = the longest prefix where the draft's
    proposal for position c equals the target's own sample at c-1
    (i.e. the draft fed the verify pass the right input at column c).
    The engine emits target columns ``0..accept_length`` — the +1 is
    the bonus/correction token, so a speculative step never emits
    fewer tokens than the non-speculative step."""
    g = 0
    while g < k and int(drafted[g]) == int(target[g]):
        g += 1
    return g
