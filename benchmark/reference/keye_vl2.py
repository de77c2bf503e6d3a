"""Plain reference for Keye-VL-2.0's language model (``model_type``
``KeyeVL2``, text tokens): the Qwen3-MoE block with a DeepSeek-Sparse-
Attention lightning indexer in every layer. float32 throughout,
``jax.numpy`` only, no kernel, no cache, no paging, no batching,
``jax.default_matmul_precision("highest")`` (on a TPU a float32 product
otherwise runs in bf16 passes).

The equations (all layers alike: ``decoder_sparse_step`` 1,
``mlp_only_layers`` []; no bias anywhere; RMSNorm ``x / sqrt(mean(x^2) +
eps) * scale``, eps ``rms_norm_eps``; RoPE rotate-half at ``rope_theta``
over all of a head's dimensions unless said otherwise)::

    x = embed[token]                                   (untied; head separate)
    per layer:
      h   = RMSNorm(x)
      q   = RoPE(RMSNorm_hd(h Wq))   H x hd            q_norm / k_norm per head
      k   = RoPE(RMSNorm_hd(h Wk))   KV x hd
      v   = h Wv                     KV x hd
      -- lightning indexer (sa_config) --
      qI_j = RoPE_r(h WIq_j)          j = 1..J, Di dims
      kI   = RoPE_r(LayerNorm(h WIk)) 1 x Di
      w_j  = (h Ww)_j * J^-1/2
      I[t,s] = Di^-1/2 * sum_j w_j[t] * ReLU(qI_j[t] . kI[s])      s <= t
      S_t  = the min(t+1, topk) positions s <= t of largest I[t,s]
      o_h[t] = sum_{s in S_t} softmax_s(q_h[t] . k_g(h)[s] / sqrt(hd)) v_g(h)[s]
      x   = x + concat_h(o_h) Wo
      h2  = RMSNorm(x)
      r   = h2 Wr  (all E experts)   E8 = top-k of r;  g = softmax over r[E8]
      x   = x + sum_{e in E8, e held} g_e * (SiLU(h2 W1u_e) * (h2 W1v_e)) W2_e
    logits = RMSNorm(x) Whead

``RoPE_r`` rotates the first ``index_rope_dim`` dimensions only. Ties at
the cutoff go to the lower position (``lax.top_k``'s order). ``held``:
the experts of this chip's share (``config["held_experts"]``); what the
absent experts would have added is left out, as in the program.

Assumptions, beside the published ``config.json`` (the configuration
file lists them under ``assumed``): q_norm / k_norm as the Qwen3-MoE
block has them; the indexer reads ``h`` (the model has no latent
query); LayerNorm (eps ``rms_norm_eps``, with bias) on the index key and
RoPE on its first 32 of 64 dimensions, as DeepSeek-V3.2's indexer;
``q_chunk_size`` / ``kv_chunk_size`` are blocking of the indexer's work
and change nothing here; text tokens only, so M-RoPE's three position ids
are equal and it is the 1-D RoPE above.

Parameters come in the program's own tree (tpu_ddp/models/sparse_moe.py:
``embed`` (V, d), ``head`` (d, V), ``ln_f``; per block ``ln1``, ``ln2``,
``wq`` (d, H, hd), ``wkv`` (d, 2, KV, hd), ``q_norm`` / ``k_norm`` (hd),
``wo`` (H, hd, d), ``index_wq`` (d, J, Di), ``index_wk`` (d, Di),
``index_norm`` (scale, bias), ``index_ww`` (d, J), ``router`` (d, E),
``w1`` (E_held, d, 2 ff), ``w2`` (E_held, ff, d)) and are raised to
float32 a layer at a time, the experts a few at a time, the head a block
of the vocabulary at a time. Scores are computed for ``QUERY_BLOCK``
queries at a time, so that 5,000 positions fit beside a serving engine.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256   # queries scored and attended together
EXPERT_GROUP = 4    # experts raised to float32 together
HEAD_ROWS = 512     # positions whose log-probabilities are made together
HEAD_COLS = 16384   # columns of the head raised to float32 together


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, pos, theta, dims):
    """Rotate-half RoPE of the first ``dims`` of the last axis of ``x``
    (L, ..., D) at positions ``pos`` (L,)."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv                # (L, half)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang), rest], -1)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _index_key(blk, h, c):
    """kI of every position of ``h`` (L, d): (L, Di)."""
    return _rope(_layer_norm(h @ blk["index_wk"], blk["index_norm"]["scale"],
                             blk["index_norm"]["bias"], c["eps"]),
                 jnp.arange(h.shape[0]), c["theta"], c["rope_r"])


def _scores(blk, hb, pb, ki, c):
    """``I`` of the queries ``hb`` (Q, d) at positions ``pb`` against the
    index keys ``ki`` (L, Di) of every position: (Q, L), not masked. A
    zero is +0, so that ``lax.top_k`` ranks it level with every other
    zero (it orders -0 below +0)."""
    qi = _rope(jnp.einsum("ld,djk->ljk", hb, blk["index_wq"]), pb,
               c["theta"], c["rope_r"])
    w = (hb @ blk["index_ww"]) / math.sqrt(qi.shape[1])
    s = jnp.einsum("qj,qjs->qs", w,
                   jax.nn.relu(jnp.einsum("qjk,sk->qjs", qi, ki))) \
        / math.sqrt(qi.shape[2])
    return jnp.where(s == 0, 0.0, s)


def _blocks(L):
    """Query positions in ``QUERY_BLOCK`` rows: (nb, QUERY_BLOCK), the
    padding at the last position."""
    nb = -(-L // QUERY_BLOCK)
    return jnp.minimum(jnp.arange(nb * QUERY_BLOCK), L - 1).reshape(
        nb, QUERY_BLOCK)


@functools.partial(jax.jit, static_argnames=("c",))
def _layer_scores(blk, x, c):
    c = dict(c)
    blk = _f32(blk)
    L = x.shape[0]
    h = _rms_norm(x, blk["ln1"]["scale"], c["eps"])
    ki = _index_key(blk, h, c)
    pos = jnp.arange(L)
    s = jax.lax.map(lambda pb: _scores(blk, h[pb], pb, ki, c), _blocks(L))
    s = s.reshape(-1, L)[:L]
    return jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)


def layer_index_scores(blk, x, config):
    """``I`` (L, L) of one layer whose input is ``x`` (L, d), ``-inf``
    above the diagonal: what the selection of that layer ranks."""
    part = {k: blk[k] for k in ("ln1", "index_wq", "index_wk", "index_norm",
                                "index_ww")}
    with jax.default_matmul_precision("highest"):
        return _layer_scores(part, x, _consts(config))


@functools.partial(jax.jit, static_argnames=("c",))
def _attention(blk, x, c):
    """The sparse attention mixer on one sequence: (out (L, d), the
    selected positions (L, K), -1 where a query has fewer than K).
    x: (L, d) float32; ``c``: (name, value) pairs of :func:`_consts`."""
    c = dict(c)
    blk = _f32(blk)
    L = x.shape[0]
    hd = blk["wq"].shape[2]
    pos = jnp.arange(L)
    h = _rms_norm(x, blk["ln1"]["scale"], c["eps"])
    q = _rope(_rms_norm(jnp.einsum("ld,dhk->lhk", h, blk["wq"]),
                        blk["q_norm"]["scale"], c["eps"]),
              pos, c["theta"], hd)
    k = _rope(_rms_norm(jnp.einsum("ld,dhk->lhk", h, blk["wkv"][:, 0]),
                        blk["k_norm"]["scale"], c["eps"]),
              pos, c["theta"], hd)
    v = jnp.einsum("ld,dhk->lhk", h, blk["wkv"][:, 1])
    ki = _index_key(blk, h, c)
    kvh = k.shape[1]
    group = q.shape[1] // kvh
    top = min(c["topk"], L)

    def block(pb):
        s = _scores(blk, h[pb], pb, ki, c)
        causal = pos[None, :] <= pb[:, None]
        _, sel = jax.lax.top_k(jnp.where(causal, s, -jnp.inf), top)
        real = sel <= pb[:, None]
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], jnp.where(real, sel, L)].set(
                True, mode="drop")
        outs = []
        for g in range(kvh):
            qg = q[pb, g * group:(g + 1) * group]             # (Q, G, hd)
            a = jnp.einsum("qhk,sk->hqs", qg, k[:, g]) / math.sqrt(hd)
            a = jnp.where(chosen[None], a, -jnp.inf)
            outs.append(jnp.einsum("hqs,sk->qhk", jax.nn.softmax(a, -1),
                                   v[:, g]))
        return jnp.concatenate(outs, axis=1), jnp.where(real, sel, -1)

    o, sel = jax.lax.map(block, _blocks(L))
    o = o.reshape((-1,) + o.shape[2:])[:L]
    return (jnp.einsum("lhk,hkd->ld", o, blk["wo"]),
            sel.reshape(-1, top)[:L])


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
def _experts(h, gates, w1, w2):
    """Sum over the experts of this group of gate times expert, every
    token through every expert (the gate is zero where not chosen)."""
    out = jnp.zeros_like(h)
    for e in range(w1.shape[0]):
        u, v = jnp.split(h @ w1[e].astype(jnp.float32), 2, axis=-1)
        out = out + gates[:, e:e + 1] * (
            (_silu(u) * v) @ w2[e].astype(jnp.float32))
    return out


def moe(blk, x, config):
    """The held experts' part of the routed sum of one layer, with ``h =
    RMSNorm(x)`` (there is no shared expert)."""
    lo, hi = config["held_experts"]
    h, gates = _route({"ln2": blk["ln2"], "router": blk["router"]}, x,
                      eps=config["rms_norm_eps"],
                      top_k=config["num_experts_per_tok"])
    out = jnp.zeros_like(h)
    for e in range(0, hi - lo, EXPERT_GROUP):
        end = min(e + EXPERT_GROUP, hi - lo)
        out = out + _experts(h, gates[:, lo + e:lo + end],
                             blk["w1"][e:end], blk["w2"][e:end])
    return out


def _consts(config) -> tuple:
    """What the attention mixer takes from the configuration, hashable."""
    return (("eps", config["rms_norm_eps"]), ("theta", config["rope_theta"]),
            ("rope_r", config["index_rope_dim"]),
            ("topk", config["sa_config"]["topk"]))


def mixer(blk, x, config):
    """``(out, selected)`` of the attention half of one layer."""
    part = {k: blk[k] for k in ("ln1", "wq", "wkv", "q_norm", "k_norm", "wo",
                                "index_wq", "index_wk", "index_norm",
                                "index_ww")}
    return _attention(part, x, _consts(config))


@functools.partial(jax.jit, static_argnames=("eps",))
def _log_softmax_rows(x, scale, head, *, eps):
    x = _rms_norm(x, scale.astype(jnp.float32), eps)
    logits = jnp.concatenate(
        [x @ head[:, v:v + HEAD_COLS].astype(jnp.float32)
         for v in range(0, head.shape[1], HEAD_COLS)], axis=-1)
    return jax.nn.log_softmax(logits, -1)


def forward(params, tokens, config, inputs=None):
    """(L,) token ids -> (the residual stream after the last layer (L, d),
    the selected positions of every layer [(L, K), ...]). ``inputs``: a
    list that is given each layer's input."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        selected = []
        for blk in params["blocks"]:
            if inputs is not None:
                inputs.append(x)
            o, sel = mixer(blk, x, config)
            selected.append(sel)
            x = x + o
            x = x + moe(blk, x, config)
        return x, selected


def log_probs(params, tokens, config):
    """(L,) token ids -> (L, V) log-probabilities of the next token, as a
    host array made ``HEAD_ROWS`` positions at a time."""
    x, _ = forward(params, tokens, config)
    L = x.shape[0]
    out = np.empty((L, params["head"].shape[1]), np.float32)
    with jax.default_matmul_precision("highest"):
        for r in range(0, L, HEAD_ROWS):
            rows = x[r:r + HEAD_ROWS]
            n = rows.shape[0]
            rows = jnp.pad(rows, ((0, HEAD_ROWS - n), (0, 0)))
            out[r:r + n] = np.asarray(_log_softmax_rows(
                rows, params["ln_f"]["scale"], params["head"],
                eps=config["rms_norm_eps"]))[:n]
    return out


def rounded(params, fmt: str):
    """The control of the log-probability limit: ``params`` with every
    weight matrix rounded to ``fmt`` (``"fp8"``: float8 e4m3, the nearest
    floating precision below the configuration's bf16; ``"int8"``), a
    scale a column (per output value, over the input axes); norms as they
    are. Returned in the parameters' own dtype."""
    top = {"int8": 127.0, "fp8": 448.0}[fmt]

    def q(path, a):
        name = jax.tree_util.keystr(path)
        if a.ndim < 2 or "norm" in name or "ln" in name:
            return a
        if "embed" in name:
            axes = (a.ndim - 1,)                   # a row is what is read
        elif "'w1'" in name or "'w2'" in name:
            axes = (1,)                            # per expert, per column
        elif "'wo'" in name:
            axes = (0, 1)
        else:
            axes = (0,)
        a32 = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(a32), axis=axes, keepdims=True) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        x = a32 / scale
        x = jnp.round(x) if fmt == "int8" else \
            x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (x * scale).astype(a.dtype)
    return jax.tree_util.tree_map_with_path(q, params)
