"""Plain reference for the ``granitemoehybrid`` family (Granite 4.0-H):
a decoder whose layers mix Mamba-2 state-space mixers with a few
attention mixers, each followed by a top-k expert layer plus a shared
gated MLP. float32 throughout, ``jax.numpy`` only, no kernel, no cache,
no batching, ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 product otherwise runs in bf16 passes). The recurrence is a plain
``lax.scan`` over positions, one token at a time; nothing is chunked.

The equations, from the published ``config.json`` (every linear layer
without bias; RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``, eps
``rms_norm_eps``):

    x = embedding_multiplier * embed[token]
    for each layer l:
        x = x + residual_multiplier * mixer_l(RMSNorm(x))
        h = RMSNorm(x)
        x = x + residual_multiplier * (experts(h) + shared(h))
    logits = (RMSNorm(x) @ embed.T) / logits_scaling        (tied head)

- *attention* mixer (``layer_types[l] == "attention"``): ``H`` query heads
  over ``KV`` key/value heads of ``hd``, no positional encoding
  (``position_embedding_type: "nope"``), scores times
  ``attention_multiplier`` (in place of ``1/sqrt(hd)``), causal softmax,
  output projection.
- *Mamba-2* mixer (``"mamba"``; ``d_inner = heads x head_dim``, ``G``
  groups, state ``N``, convolution ``K``):
  ``[z, xBC, dt] = h @ W_in`` (``d_inner``, ``d_inner + 2 G N``, ``heads``
  columns); ``xBC = SiLU(causal depthwise conv_K(xBC) + b_conv)``, split
  into ``x`` (d_inner), ``B`` (G N), ``C`` (G N);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; per head,
  with state ``S`` (head_dim x N), from ``S = 0``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t * outer(x_t, B_t)``,
  ``y_t = S_t C_t + D x_t``;
  ``y = RMSNorm(y * SiLU(z))`` over all of ``d_inner`` (gate before the
  norm, one norm group); ``out = y @ W_out``. No clamp on ``dt``.
- *experts*: ``r = h @ W_r`` over ALL published experts; the
  ``num_experts_per_tok`` largest; ``g`` = softmax over those chosen
  logits, in float32; expert ``e``: ``(SiLU(u) * v) @ W2_e`` with
  ``[u, v] = h @ W1_e``; the sum of ``g_e`` times expert ``e`` over the
  chosen experts **that are held** (``config["held_experts"] = [lo, hi)``,
  the chip's share of a layer divided over several chips; what the absent
  experts would have added is left out, as in the program).
- *shared*: the same gated form at ``shared_intermediate_size``, every
  token, weight 1.

Parameters come in the program's own tree (so both sides run on the same
weights) and are raised to float32 a layer at a time, the experts a few
at a time, so that the reference fits beside a serving engine:
``embed`` (V, d), ``ln_f.scale``; per block ``ln1.scale``, ``ln2.scale``,
``router`` (d, E_all), ``w1`` (E_held, d, 2 ff), ``w2`` (E_held, ff, d),
``shared_w1`` (d, 2 sff), ``shared_w2`` (sff, d); an attention block has
``wq`` (d, H, hd), ``wkv`` (d, 2, KV, hd), ``wo`` (H, hd, d); a Mamba-2
block ``in_proj`` (d, 2 d_inner + 2 G N + heads), ``conv_w`` (K, d_inner +
2 G N; row ``j`` multiplies the input ``K - 1 - j`` positions back),
``conv_b``, ``dt_bias``, ``A_log``, ``D`` (heads), ``norm.scale``
(d_inner), ``out_proj`` (d_inner, d). The kind of a block is read from
its keys.

Departures from the published model: none in the equations. What the
``config.json`` does not state (initial distributions, the state's
dtype) is listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EXPERT_GROUP = 6    # experts raised to float32 together
HEAD_ROWS = 16384   # rows of the tied embedding raised together


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _attention(blk, x, *, eps, scale):
    """The attention mixer on one sequence. x: (L, d) float32."""
    blk = _f32(blk)
    L = x.shape[0]
    h = _rms_norm(x, blk["ln1"]["scale"], eps)
    q = jnp.einsum("ld,dhk->lhk", h, blk["wq"])
    kv = jnp.einsum("ld,dcgk->lcgk", h, blk["wkv"])
    k, v = kv[:, 0], kv[:, 1]
    groups = k.shape[1]
    pos = jnp.arange(L)
    causal = pos[:, None] >= pos[None, :]
    outs = []
    for g, qg in enumerate(jnp.split(q, groups, axis=1)):
        s = jnp.einsum("qhk,tk->hqt", qg, k[:, g]) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqt,tk->qhk", jax.nn.softmax(s, -1),
                               v[:, g]))
    o = jnp.concatenate(outs, axis=1)
    return jnp.einsum("lhk,hkd->ld", o, blk["wo"])


@functools.partial(jax.jit, static_argnames=("eps", "groups"))
def _mamba(blk, x, *, eps, groups):
    """The Mamba-2 mixer on one sequence from zero state, one position
    at a time. x: (L, d) float32."""
    blk = _f32(blk)
    L = x.shape[0]
    heads = blk["dt_bias"].shape[0]
    d_inner = blk["out_proj"].shape[0]
    K, conv_dim = blk["conv_w"].shape
    n = (conv_dim - d_inner) // (2 * groups)
    h = _rms_norm(x, blk["ln1"]["scale"], eps)
    zxbcdt = h @ blk["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim)), xbc])
    conv = sum(blk["conv_w"][j] * padded[j:j + L] for j in range(K))
    xbc = _silu(conv + blk["conv_b"])
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + groups * n], axis=-1)
    xs = xs.reshape(L, heads, -1)
    per = heads // groups       # heads that share one B and C
    b = jnp.repeat(b.reshape(L, groups, n), per, axis=1)
    c = jnp.repeat(c.reshape(L, groups, n), per, axis=1)
    dt = jax.nn.softplus(dt + blk["dt_bias"])               # (L, heads)
    a = -jnp.exp(blk["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        y_t = jnp.einsum("hpn,hn->hp", state, c_t) \
            + blk["D"][:, None] * x_t
        return state, y_t

    zero = jnp.zeros((heads, xs.shape[-1], n))
    _, y = jax.lax.scan(step, zero, (xs, b, c, dt))
    y = y.reshape(L, d_inner) * _silu(z)
    y = _rms_norm(y, blk["norm"]["scale"], eps)
    return y @ blk["out_proj"]


@functools.partial(jax.jit, static_argnames=("eps", "top_k"))
def _route(blk, x, *, eps, top_k):
    """Normalised input of the MLP half and the gate of every expert
    (zero where it was not chosen): (L, d), (L, E_all)."""
    h = _rms_norm(x, blk["ln2"]["scale"].astype(jnp.float32), eps)
    logits = h @ blk["router"].astype(jnp.float32)
    vals, idx = jax.lax.top_k(logits, top_k)
    g = jax.nn.softmax(vals, -1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(logits.shape[0])[:, None], idx].set(g)
    return h, gates


@jax.jit
def _gated(h, w1, w2):
    u, v = jnp.split(h @ w1.astype(jnp.float32), 2, axis=-1)
    return (_silu(u) * v) @ w2.astype(jnp.float32)


@jax.jit
def _experts(h, gates, w1, w2):
    """Sum over the experts of this group of gate times expert, every
    token through every expert (the gate is zero where not chosen)."""
    out = jnp.zeros_like(h)
    for e in range(w1.shape[0]):
        out = out + gates[:, e:e + 1] * _gated(h, w1[e], w2[e])
    return out


def moe(blk, x, config):
    """``experts(h) + shared(h)`` of one layer, with ``h = RMSNorm(x)``:
    the held experts' part of the routed sum and the shared MLP once."""
    lo, hi = config["held_experts"]
    h, gates = _route({"ln2": blk["ln2"], "router": blk["router"]}, x,
                      eps=config["rms_norm_eps"],
                      top_k=config["num_experts_per_tok"])
    out = _gated(h, blk["shared_w1"], blk["shared_w2"])
    for e in range(0, hi - lo, EXPERT_GROUP):
        end = min(e + EXPERT_GROUP, hi - lo)
        out = out + _experts(h, gates[:, lo + e:lo + end],
                             blk["w1"][e:end], blk["w2"][e:end])
    return out


def mixer(blk, x, config):
    if "in_proj" in blk:
        part = {k: blk[k] for k in ("ln1", "in_proj", "conv_w", "conv_b",
                                    "dt_bias", "A_log", "D", "norm",
                                    "out_proj")}
        return _mamba(part, x, eps=config["rms_norm_eps"],
                      groups=config["mamba_n_groups"])
    part = {k: blk[k] for k in ("ln1", "wq", "wkv", "wo")}
    return _attention(part, x, eps=config["rms_norm_eps"],
                      scale=config["attention_multiplier"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(scale, x, *, eps):
    return _rms_norm(x, scale.astype(jnp.float32), eps)


@jax.jit
def _logits(x, rows):
    return x @ rows.astype(jnp.float32).T


def _head(params, x, *, eps, scaling):
    """The tied head, a block of the vocabulary at a time."""
    x = _final_norm(params["ln_f"]["scale"], x, eps=eps)
    embed = params["embed"]
    logits = jnp.concatenate(
        [_logits(x, embed[v:v + HEAD_ROWS])
         for v in range(0, embed.shape[0], HEAD_ROWS)], axis=-1)
    return jax.nn.log_softmax(logits / scaling, -1)


def log_probs(params, tokens, config):
    """(L,) token ids -> (L, V) log-probabilities of the next token."""
    res = config["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        x = config["embedding_multiplier"] \
            * params["embed"][tokens].astype(jnp.float32)
        for blk in params["blocks"]:
            x = x + res * mixer(blk, x, config)
            x = x + res * moe(blk, x, config)
        return _head({"ln_f": params["ln_f"], "embed": params["embed"]},
                     x, eps=config["rms_norm_eps"],
                     scaling=config["logits_scaling"])
