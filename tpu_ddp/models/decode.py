"""Shared KV-cache decode core — ONE home for the incremental-attention
math, used by both :func:`tpu_ddp.models.generate.generate` (batch
offline sampling) and the continuous-batching serving engine
(tpu_ddp/serve/). The two callers differ only in cache LAYOUT (one
contiguous ``(B, max_len, KV, hd)`` buffer per block vs the serve
engine's block-paged pool, tpu_ddp/serve/kv_pool.py); the projection,
attention, and MLP math is these functions, so "the engine decodes the
same distribution the trainer optimized" is a property of one module,
tested once (tests/test_generate.py exactness vs ``apply``,
tests/test_serve.py engine-vs-generate parity).

Position handling is the one generalization over the original
``generate.py`` internals: :func:`attend_cached` accepts per-batch-row
query positions ``(B, Lq)`` in addition to the shared ``(Lq,)`` form,
because under continuous batching every live sequence sits at its own
offset (models/transformer.py ``rope`` accepts the same two forms).
The ``(Lq,)`` path traces the exact pre-refactor program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30

# The serving features that assume every layer's cache is a list of
# positions, and why each cannot take a model with recurrent state as it
# stands (check_state_servable).
_STATE_REFUSALS = {
    "prefix_cache": "a shared prefix's K/V pages can be adopted, the "
                    "recurrent state at the end of the prefix cannot: "
                    "no snapshot of it is kept",
    "kv_tiers": "the tiered step programs carry no state pool",
    "spec_k": "a rejected draft token has already advanced the state, "
              "and a state cannot be rolled back like a block table",
    "cp_prefill": "the chunk's scan is sequential over positions; the "
                  "context-parallel step shards them",
    "decode_quant": "ops/quant.quantize_params knows the dense block's "
                    "matrices only",
    "mesh": "the state pool and the expert layer have no sharding rules",
    "disagg": "the edge ships K/V blocks only; the state of a finished "
              "prefill would stay behind",
}


def check_decodable(model) -> None:
    """Refuse model configs the decode path cannot serve. Sharded
    (sp/tp/ep) configs hold parameters in training layouts — the
    checkpoint is canonical, so materialize dense serving params first
    (:func:`dense_params_from_checkpoint` is the one-call path)."""
    if model.sp_axis is not None or model.tp_axis is not None \
            or model.ep_axis is not None:
        raise ValueError(
            "decode runs dense single-device models; drop the sp/tp/ep "
            "configuration and load the training checkpoint into a "
            "dense model — dense_params_from_checkpoint(model, ckpt_dir)"
            " (tpu_ddp/models/decode.py) does exactly that via the "
            "canonical checkpoint path")


def check_state_servable(model, **features) -> None:
    """Refuse, with the reason, a model that keeps recurrent state
    (``model.state_shapes``) under a serving feature that cannot carry
    it. ``features``: keys of ``_STATE_REFUSALS``, true where the caller
    has the feature in use. One place, so that such a model is never
    half served."""
    on = [k for k, v in features.items() if v]
    if on and model.state_shapes(1):
        raise ValueError(
            f"{model.name} keeps recurrent state per sequence, which "
            "these serving features cannot carry yet: " + "; ".join(
                f"{k} ({_STATE_REFUSALS[k]})" for k in on))


def gated_mlp(model, y, w1, w2):
    """SiLU-gated MLP: ``(SiLU(u) * v) @ w2`` with ``[u, v] = y @ w1``."""
    cd = model.compute_dtype
    uv = jnp.einsum("...d,de->...e", y, w1.astype(cd),
                    preferred_element_type=jnp.float32)
    u, v = jnp.split(uv, 2, axis=-1)
    act = (jax.nn.silu(u) * v).astype(cd)
    return jnp.einsum("...e,ed->...d", act, w2.astype(cd),
                      preferred_element_type=jnp.float32)


def mlp(model, blk, y):
    """Block MLP on a decode/prefill activation bank ``y`` (B, L, dm).

    A model that says which experts it holds (``model.held``) runs the
    dropless expert layer over its share (parallel/moe.py
    ``dropless_moe``, scope ``moe``) plus the shared gated MLP (scope
    ``shared_mlp``), summed in float32.

    Dense models run the two qdot matmuls (fp or fused int8). MoE
    models run the routed layer (tpu_ddp/parallel/moe.py) with the
    expert axis UNSHARDED — serving params are dense — and capacity
    computed by ``moe_mlp`` from the LIVE bank size T = B*L (the slot
    bank for a decode step, the chunk for prefill), not the training
    batch. Routing is per-token, so with capacity admitting every
    token (the serve engine sizes ``moe_capacity_factor`` so the E
    queues cover the bank; tests pin greedy-stream parity vs ``apply``)
    each token's output is independent of its batch neighbors — the
    property that makes incremental decode match the whole-sequence
    forward despite capacity competition happening per step here and
    per sequence there. At tight capacity the two CAN diverge (tokens
    drop in one composition and not the other); that trade is the
    operator's, surfaced as the dropped-token counter, never silent.
    """
    from tpu_ddp.ops.quant import qdot
    cd = model.compute_dtype
    if getattr(model, "held", None) is not None:
        from tpu_ddp.parallel.moe import dropless_moe
        with jax.named_scope("moe"):
            out = dropless_moe(
                y.reshape(-1, y.shape[-1]), blk["router"], blk["w1"],
                blk["w2"], top_k=model.top_k, held=model.held)
        with jax.named_scope("shared_mlp"):
            out = out.reshape(y.shape) + gated_mlp(
                model, y, blk["shared_w1"], blk["shared_w2"])
        return out.astype(cd)
    if model.moe_experts:
        from tpu_ddp.parallel.moe import moe_mlp
        out, _ = moe_mlp(
            y, blk["router"], blk["w1"], blk["w2"],
            num_experts=model.moe_experts,
            capacity_factor=model.moe_capacity_factor,
            top_k=model.moe_top_k, ep_size=1)
        return out.astype(cd)
    y = qdot(y, blk["w1"], cd)
    y = jax.nn.gelu(y.astype(jnp.float32)).astype(cd)
    return qdot(y, blk["w2"], cd).astype(cd)


def attn_scale(model) -> float:
    """What attention scores are multiplied by: the model's own
    ``attn_scale`` if it states one, else ``1/sqrt(head_dim)``."""
    scale = getattr(model, "attn_scale", None)
    return 1.0 / (model.head_dim ** 0.5) if scale is None else scale


def attend_cached(model, q, ck, cv, q_pos):
    """q: (B, Lq, H, hd) at absolute positions ``q_pos`` — (Lq,) shared
    across the batch, or (B, Lq) per row (continuous batching); ck/cv:
    full (B, S, KV, hd) cache views. Attends each query over cache
    positions <= its own — the causal mask also covers not-yet-written
    (or stale, for the paged pool) slots: their positions exceed every
    live query's, and the masked ``exp(-1e30 - max)`` underflows to an
    exact 0 weight, so garbage beyond the live length can never leak
    into the output. Under GQA the grouped einsum contracts Q heads
    (B, Lq, KV, G, hd) directly against the KV-width cache — the
    expansion is never materialized, preserving the smaller cache's
    bandwidth win (decode is KV-read-bound)."""
    scale = attn_scale(model)
    b, lq, h, hd = q.shape
    kv = ck.shape[2]
    qg = q.reshape(b, lq, kv, h // kv, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(ck.shape[1])
    q_pos = jnp.asarray(q_pos)
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]
    mask = k_pos[None, None, None, None, :] \
        > qp[:, None, None, :, None]
    scores = jnp.where(mask, _NEG_INF, scores)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, cv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, lq, h, hd).astype(q.dtype)


def project_qkv(model, blk, x, pos):
    """Pre-attention half of a block: LN1 + the training-path QKV
    projection with RoPE at ``pos`` ((L,) or (B, L)). The caller owns
    writing k/v into ITS cache layout before attending."""
    y = model.norm(x, blk["ln1"])
    return model.qkv_proj(blk, y, pos)


def residual(model, x, o):
    """``x + o``, times the model's ``residual_multiplier`` if it has
    one."""
    m = getattr(model, "residual_multiplier", 1.0)
    return x + o if m == 1.0 else x + o * jnp.asarray(m, o.dtype)


def mlp_half(model, blk, x):
    """The MLP half of a block of either mixer: norm, MLP, residual."""
    with jax.named_scope("mlp"):
        y = model.norm(x, blk["ln2"])
        return residual(model, x, mlp(model, blk, y))


def block_finish(model, blk, x, o):
    """Post-attention half of a block: output projection + residual,
    LN2 + MLP + residual. (B, L, dm) -> (B, L, dm)."""
    from tpu_ddp.ops.quant import qdot
    cd = model.compute_dtype
    b, L = x.shape[0], x.shape[1]
    with jax.named_scope("attn"):
        o = qdot(o.reshape(b, L, -1), blk["wo"], cd,
                 reshape=(-1, model.d_model)).astype(cd)
        x = residual(model, x, o)
    return mlp_half(model, blk, x)


def ssm_mix(model, blk, x, ssm, conv, n_valid=None, advance=None):
    """The state-space mixer's half of a block, the twin of
    project_qkv + attention + output projection: norm, the Mamba-2 mixer
    from the given state, residual. One token for each of S sequences
    (``x`` (S, 1, dm), ``ssm`` / ``conv`` with a leading S) or, with
    ``n_valid``, a run of ONE sequence (``x`` (1, C, dm)) of which the
    first ``n_valid`` rows count. ``advance`` is ``ssm_step``'s. Returns
    (x, ssm, conv); the caller owns reading and writing ITS state
    layout."""
    from tpu_ddp.models.hybrid import ssm_chunk, ssm_step
    h = model.norm(x, blk["ln1"])
    if n_valid is None:
        o, ssm, conv = ssm_step(model, blk, h[:, 0], ssm, conv, advance)
        o = o[:, None]
    else:
        o, ssm, conv = ssm_chunk(model, blk, h[0], ssm, conv, n_valid)
        o = o[None]
    return residual(model, x, o), ssm, conv


def forward_cached(model, params, tokens, caches, start: int):
    """Run ``tokens`` (B, L) occupying absolute positions
    ``start..start+L-1`` against (and updating) contiguous
    (B, max_len, KV, hd) caches. Returns (last-position logits (B, V),
    new caches). The ``generate()`` path; the serve engine's paged
    twin (tpu_ddp/serve/engine.py) is the same project/attend/finish
    sequence over pool-gathered cache views."""
    cd = model.compute_dtype
    b, L = tokens.shape
    pos = start + jnp.arange(L)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cd)
    new_caches = []
    for blk, (ck, cv) in zip(params["blocks"], caches):
        with jax.named_scope("attn"):
            q, k, v = project_qkv(model, blk, x, pos)
            ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, start, 0, 0))
            cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, start, 0, 0))
            o = attend_cached(model, q, ck, cv, pos)
        x = block_finish(model, blk, x, o)
        new_caches.append((ck, cv))
    logits = model.head_apply(params, x[:, -1:])[:, 0]
    return logits, tuple(new_caches)


def init_cache(model, batch: int, max_len: int):
    """Per-block (K, V) buffers: (B, max_len, KV, hd) each — under GQA
    the cache is num_heads/num_kv_heads times smaller than MHA's, the
    scheme's reason to exist (decode is KV-cache-bandwidth-bound)."""
    shape = (batch, max_len, model.kv_heads, model.head_dim)
    zeros = jnp.zeros(shape, model.compute_dtype)
    return tuple((zeros, zeros) for _ in range(model.num_layers))


def sample_token(model, logits, temperature, seed, position):
    """The ONE sampling rule for serving: greedy argmax at
    ``temperature == 0``, else categorical at the given temperature,
    keyed deterministically by (per-request ``seed``, the sequence
    ``position`` the sampled token will occupy) — stateless, so a
    retried or resumed request re-samples identically. Returns
    (token, logprob-of-token), both scalars; vmap over the live batch
    for the continuous-batching step."""
    key = jax.random.fold_in(jax.random.key(seed), position)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    greedy = jnp.argmax(logits).astype(jnp.int32)
    tok = jnp.where(temperature > 0, sampled, greedy)
    logprob = jax.nn.log_softmax(logits.astype(jnp.float32))[tok]
    return tok, logprob


def verify_sample(model, logits, temperature, seed, positions):
    """Batched multi-position sampling for speculative verification:
    ``logits`` (W, V) at ``positions`` (W,) under ONE request's
    (temperature, seed) -> (tokens (W,), logprobs (W,)). Each column
    is exactly :func:`sample_token` with the same stateless
    ``fold_in(seed, position)`` key the one-token decode step would
    use at that position — the property that makes the speculative
    accept path bitwise identical to the non-speculative stream
    (tpu_ddp/serve/speculative.py, DESIGN.md §26). vmap over the live
    batch for the verify program."""
    return jax.vmap(
        lambda lg, p: sample_token(model, lg, temperature, seed, p)
    )(logits, positions)


def dense_params_from_checkpoint(model, directory: str,
                                 step: int | None = None):
    """Sharded-training-checkpoint -> dense serving params, one call.

    Checkpoints are written in CANONICAL (dense, global) shapes by
    every trainer — the vision engine routes through
    ``Trainer.state_to_host`` and the LM trainers through their
    gather + canonicalize path — precisely so any strategy's artifact
    restores anywhere. This helper reads ONLY the ``params`` subtree
    against the dense model's template (optimizer state, step counter
    and any compression carry are dropped), digest-verifying each leaf
    (utils/checkpoint.py), and returns a pytree :func:`generate`'s /
    the serve engine's dense math accepts directly. ``model`` must be
    the dense config (no sp/tp/ep axes; drop them with
    ``dataclasses.replace`` if you hold the training-time config —
    the parameter TREE is identical, only the runtime layout differs).
    """
    check_decodable(model)
    from tpu_ddp.utils.checkpoint import restore_checkpoint
    template = {"params": jax.eval_shape(
        lambda: model.init(jax.random.key(0)))}
    restored, _ = restore_checkpoint(
        directory, template, step,
        drop_extra=("opt_state", "step", "comp_state"))
    return jax.tree.map(jnp.asarray, restored["params"])
