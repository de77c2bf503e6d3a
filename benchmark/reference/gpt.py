"""Plain reference for the dense decoder the repo's ``TransformerLM``
implements: float32 throughout, ``jax.numpy`` only, no kernel, no
cache, no batching tricks, ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product otherwise runs in bf16 passes).

The block is GPT-style pre-LayerNorm: LayerNorm (eps 1e-5, scale and
bias), rotary position embedding over the whole head (rotate-half, base
10000), causal softmax attention with grouped K/V heads, tanh GELU MLP,
final LayerNorm, untied output head, no bias on the linear layers. These
are the repo's equations (``tpu_ddp/models/transformer.py``) and what
the configuration files list under ``departures`` from StarCoder2's.

Parameters come in the program's own tree, so that both sides run on the
same weights: ``embed`` (V, d), ``head`` (d, V), ``ln_f``, and per block
``ln1``, ``wq`` (d, H, hd), ``wkv`` (d, 2, KV, hd), ``wo`` (H, hd, d),
``ln2``, ``w1`` (d, ff), ``w2`` (ff, d).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ROPE_BASE = 10000.0
LN_EPS = 1e-5


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rope(x, positions):
    """x: (L, heads, hd)."""
    half = x.shape[-1] // 2
    freqs = ROPE_BASE ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(blk, x):
    """One decoder block on one sequence. x: (L, d) float32."""
    blk = _f32(blk)
    L = x.shape[0]
    pos = jnp.arange(L)
    y = _layer_norm(x, blk["ln1"])
    q = _rope(jnp.einsum("ld,dhk->lhk", y, blk["wq"]), pos)
    kv = jnp.einsum("ld,dcgk->lcgk", y, blk["wkv"])
    k, v = _rope(kv[:, 0], pos), kv[:, 1]
    groups = k.shape[1]
    causal = pos[:, None] >= pos[None, :]
    outs = []
    # One K/V group at a time, so that the L x L scores of only
    # heads/groups query heads are alive together.
    for g, qg in enumerate(jnp.split(q, groups, axis=1)):
        s = jnp.einsum("qhk,tk->hqt", qg, k[:, g]) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqt,tk->qhk", jax.nn.softmax(s, -1),
                               v[:, g]))
    o = jnp.concatenate(outs, axis=1)
    x = x + jnp.einsum("lhk,hkd->ld", o, blk["wo"])
    y = _layer_norm(x, blk["ln2"])
    y = jax.nn.gelu(y @ blk["w1"], approximate=True) @ blk["w2"]
    return x + y


_block = jax.jit(block)


@jax.jit
def _head(params, x):
    x = _layer_norm(x, _f32(params["ln_f"]))
    return jax.nn.log_softmax(x @ params["head"].astype(jnp.float32), -1)


def log_probs(params, tokens):
    """(L,) token ids -> (L, V) log-probabilities of the next token.
    One jitted block called once per layer: one small program whatever
    the depth."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for blk in params["blocks"]:
            x = _block(blk, x)
        return _head({"ln_f": params["ln_f"], "head": params["head"]}, x)


def loss(params, inputs, targets):
    """Mean next-token cross-entropy of one (L,) sequence."""
    lp = log_probs(params, inputs)
    return -jnp.mean(jnp.take_along_axis(lp, targets[:, None], -1))
