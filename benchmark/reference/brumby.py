"""Plain reference for Brumby-14B-Base (``model_type`` ``brumby``): the
Qwen3 block with every attention layer replaced by gated symmetric-power
retention of degree 2 (Buckman, Gelada et al., "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239; Manifest AI's ``retention``
library). float32 throughout, ``jax.numpy`` only, no kernel, no state,
no cache, no paging, no batching, ``jax.default_matmul_precision
("highest")`` (on a TPU a float32 product otherwise runs in bf16 passes).
The QUADRATIC form, computed ``QUERY_BLOCK`` queries at a time: the
program's recurrent state appears nowhere here.

The equations (all layers alike, one period is one layer; no bias but
the gate's; RMSNorm ``x / sqrt(mean(x^2) + eps) * scale``, eps
``rms_norm_eps``; RoPE rotate-half at ``rope_theta`` over all 128
dimensions of a head)::

    x = embed[token]                                   (untied; head separate)
    per layer:
      h    = RMSNorm(x)
      q_i  = RoPE(RMSNorm_hd((h Wq)_i)) / sqrt(hd)   i = 1..H    q_norm / k_norm
      k_g  = RoPE(RMSNorm_hd((h Wk)_g))              g = 1..KV
      v_g  = (h Wv)_g
      a_g  = log sigmoid((h Wgate + bgate)_g)        one gate a K/V head
      w_ts = (q_i,t . k_g(i),s)^2 * exp(sum_{r=s+1..t} a_g(i),r)    s <= t
      o_i,t = sum_s w_ts v_g(i),s / (sum_s w_ts + eps)              g(i) = i // (H/KV)
      x    = x + concat_i(o_i) Wo
      h2   = RMSNorm(x)
      x    = x + (SiLU(h2 Wgate_mlp) * (h2 Wup)) Wdown
    logits = RMSNorm(x) Whead

which is, with ``phi(u) = (c_ij u_i u_j)_{i<=j}`` (``c_ii = 1``,
``c_ij = sqrt 2``; ``phi(u) . phi(w) = (u . w)^2``), the recurrence
``S_t = exp(a_t) S_{t-1} + phi(k_t) v_t^T``, ``z_t = exp(a_t) z_{t-1} +
phi(k_t)``, ``o_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` that the
program keeps (tpu_ddp/models/retention.py).

Assumptions, beside the published ``config.json`` (the configuration
file lists them under ``assumed`` and their numbers under
``power_retention``): degree 2; the gate's form and width; q_norm /
k_norm as the Qwen3 block has them; the scale ``1/sqrt(head_dim)`` on q;
eps 1e-6.

Parameters come in the program's own tree (``embed`` (V, d), ``head``
(d, V), ``ln_f``; per block ``ln1``, ``ln2``, ``wq`` (d, H, hd), ``wkv``
(d, 2, KV, hd), ``q_norm`` / ``k_norm`` (hd), ``wo`` (H, hd, d),
``w_gate`` (d, KV), ``b_gate`` (KV,), ``w1`` (d, 2 ff: gate then up),
``w2`` (ff, d)) and are raised to float32 a layer at a time, the head a
block of the vocabulary at a time, so that 5,000 positions fit beside a
serving engine.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128   # queries weighed together (x 8: rows of the MLP)
HEAD_ROWS = 512     # positions whose log-probabilities are made together
HEAD_COLS = 16384   # columns of the head raised to float32 together


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate-half RoPE of the last axis of ``x`` (L, heads, D) at
    positions ``pos`` (L,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # (L, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _blocks(L):
    """Query positions in ``QUERY_BLOCK`` rows: (nb, QUERY_BLOCK), the
    padding at the last position."""
    nb = -(-L // QUERY_BLOCK)
    return jnp.minimum(jnp.arange(nb * QUERY_BLOCK), L - 1).reshape(
        nb, QUERY_BLOCK)


@functools.partial(jax.jit, static_argnames=("c",))
def _retention(blk, x, c):
    """The retention mixer on one sequence, the quadratic form: (L, d).
    x: (L, d) float32; ``c``: (name, value) pairs of :func:`_consts`."""
    c = dict(c)
    blk = _f32(blk)
    L = x.shape[0]
    hd = blk["wq"].shape[2]
    pos = jnp.arange(L)
    h = _rms_norm(x, blk["ln1"]["scale"], c["eps"])
    q = _rope(_rms_norm(jnp.einsum("ld,dhk->lhk", h, blk["wq"]),
                        blk["q_norm"]["scale"], c["eps"]),
              pos, c["theta"]) / math.sqrt(hd)
    k = _rope(_rms_norm(jnp.einsum("ld,dhk->lhk", h, blk["wkv"][:, 0]),
                        blk["k_norm"]["scale"], c["eps"]), pos, c["theta"])
    v = jnp.einsum("ld,dhk->lhk", h, blk["wkv"][:, 1])
    a = jax.nn.log_sigmoid(h @ blk["w_gate"] + blk["b_gate"])   # (L, KV)
    A = jnp.cumsum(a, axis=0)
    kvh = k.shape[1]
    group = q.shape[1] // kvh

    def block(pb):
        causal = pos[None, :] <= pb[:, None]                    # (Q, L)
        outs = []
        for g in range(kvh):
            qg = q[pb, g * group:(g + 1) * group]               # (Q, G, hd)
            w = jnp.einsum("qhk,sk->hqs", qg, k[:, g]) ** 2
            gap = A[pb, g][:, None] - A[None, :, g]             # (Q, L)
            w = w * jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)),
                              0.0)[None]
            outs.append(jnp.einsum("hqs,sk->qhk", w, v[:, g])
                        / (jnp.sum(w, -1).T[..., None] + c["ret_eps"]))
        return jnp.concatenate(outs, axis=1)

    o = jax.lax.map(block, _blocks(L))
    o = o.reshape((-1,) + o.shape[2:])[:L]
    return jnp.einsum("lhk,hkd->ld", o, blk["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp(blk, x, *, eps):
    """The MLP half of one layer, ``QUERY_BLOCK`` x 8 rows at a time."""
    blk = _f32(blk)
    L = x.shape[0]
    n = 8 * QUERY_BLOCK
    rows = jnp.pad(x, ((0, -L % n), (0, 0))).reshape(-1, n, x.shape[1])

    def part(xb):
        h = _rms_norm(xb, blk["ln2"]["scale"], eps)
        gate, up = jnp.split(h @ blk["w1"], 2, axis=-1)
        return (_silu(gate) * up) @ blk["w2"]

    return jax.lax.map(part, rows).reshape(-1, x.shape[1])[:L]


def _consts(config) -> tuple:
    """What the retention mixer takes from the configuration, hashable."""
    return (("eps", config["rms_norm_eps"]), ("theta", config["rope_theta"]),
            ("ret_eps", config["power_retention"]["eps"]))


def mixer(blk, x, config):
    """The retention half of one layer."""
    part = {k: blk[k] for k in ("ln1", "wq", "wkv", "q_norm", "k_norm",
                                "wo", "w_gate", "b_gate")}
    return _retention(part, x, _consts(config))


@functools.partial(jax.jit, static_argnames=("eps",))
def _log_softmax_rows(x, scale, head, *, eps):
    x = _rms_norm(x, scale.astype(jnp.float32), eps)
    logits = jnp.concatenate(
        [x @ head[:, v:v + HEAD_COLS].astype(jnp.float32)
         for v in range(0, head.shape[1], HEAD_COLS)], axis=-1)
    return jax.nn.log_softmax(logits, -1)


def forward(params, tokens, config, fmt=None):
    """(L,) token ids -> the residual stream after the last layer (L, d).
    ``fmt``: each weight rounded to it as it is used (:func:`rounded`)."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        if fmt:     # a row of the embedding is an output value
            x = rounded({"rows": x.T}, fmt)["rows"].T
        x = x.astype(jnp.float32)
        for blk in params["blocks"]:
            blk = rounded(blk, fmt) if fmt else blk
            x = x + mixer(blk, x, config)
            x = x + _mlp({k: blk[k] for k in ("ln2", "w1", "w2")}, x,
                         eps=eps)
        return x


def log_probs(params, tokens, config, fmt=None):
    """(L,) token ids -> (L, V) log-probabilities of the next token, as a
    host array made ``HEAD_ROWS`` positions at a time. ``fmt``: the
    control of the check's limit, every weight rounded to ``"fp8"`` as
    it is used."""
    x = forward(params, tokens, config, fmt)
    head = params["head"]
    if fmt:
        head = jnp.concatenate(
            [rounded({"head": head[:, v:v + HEAD_COLS]}, fmt)["head"]
             for v in range(0, head.shape[1], HEAD_COLS)], axis=1)
    L = x.shape[0]
    out = np.empty((L, params["head"].shape[1]), np.float32)
    with jax.default_matmul_precision("highest"):
        for r in range(0, L, HEAD_ROWS):
            rows = x[r:r + HEAD_ROWS]
            n = rows.shape[0]
            rows = jnp.pad(rows, ((0, HEAD_ROWS - n), (0, 0)))
            out[r:r + n] = np.asarray(_log_softmax_rows(
                rows, params["ln_f"]["scale"], head,
                eps=config["rms_norm_eps"]))[:n]
    return out


def rounded(tree, fmt: str):
    """``tree`` with every weight matrix rounded to ``fmt`` (``"fp8"``:
    float8 e4m3, the nearest floating precision below the
    configuration's bf16), a scale an output value: a column,
    over all the input axes (``wo``'s heads and head dimension, the first
    axis of the rest). Norms, the gate's bias and other vectors as they
    are; returned in the parameters' own dtype."""
    top = {"fp8": 448.0}[fmt]

    def q(path, a):
        name = jax.tree_util.keystr(path)
        if a.ndim < 2 or "norm" in name or "ln" in name:
            return a
        axes = (0, 1) if "'wo'" in name else (0,)
        a32 = a.astype(jnp.float32)
        scale = jnp.max(jnp.abs(a32), axis=axes, keepdims=True) / top
        scale = jnp.where(scale > 0, scale, 1.0)
        x = a32 / scale
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (x * scale).astype(a.dtype)
    return jax.tree_util.tree_map_with_path(q, tree)
