"""``RetentionLM``: an attention-free decoder whose every layer is gated
symmetric-power retention of degree 2 (Manifest AI's power retention,
arXiv:2507.04239) in the Qwen3 block: RMSNorm, per-head q / k RMSNorm,
rotate-half RoPE, GQA, a SwiGLU MLP, an untied head, no bias but the
gate's.

Per layer, for K/V head ``g`` and the queries ``i`` of its group
(``h = RMSNorm(x)``; ``q`` scaled by ``1/sqrt(head_dim)``)::

    a_g,t = log sigmoid((h Wg + bg)_g)             one gate a K/V head
    phi(u) = (c_ij u_i u_j) for i <= j, c_ii = 1, c_ij = sqrt 2 (i < j)
             so that phi(u) . phi(w) = (u . w)^2
    S_g,t = exp(a_g,t) S_g,t-1 + phi(k_g,t) v_g,t^T      (F x hd)
    z_g,t = exp(a_g,t) z_g,t-1 + phi(k_g,t)              (F)
    o_i,t = phi(q_i,t)^T S_g,t / (phi(q_i,t)^T z_g,t + eps)

with ``F = hd (hd + 1) / 2`` features (8,256 at a head of 128: the
symmetric power, half the 16,384 of the full tensor power for the same
numbers). The same output as the quadratic form
``w_ts = (q_t . k_s)^2 exp(sum_{r=s+1..t} a_r)``, ``o_t = sum_s w_ts v_s /
(sum_s w_ts + eps)``, which the plain reference computes
(benchmark/reference/brumby.py).

Two forms of the one recurrence: :func:`retention_step`, one token for
a bank of sequences, read out of the OLD state (``exp(a) phi(q)^T S +
(q . k)^2 v``, the same number), so that a step reads the state once for
the readout and once for its update; and :func:`retention_chunk`, a run
of one sequence from its incoming state: quadratic within the run,
through the state across runs. The state is float32, what the reference
computes in: narrower is another result, not a faster one, and every
product that reads or makes it runs at full float32 precision.

Serving only: the model offers what the decode core
(tpu_ddp/models/decode.py) and ``ServeEngine`` ask of a model, as
``HybridLM`` does; every layer's mixer is ``"power_retention"``, so the
engine's paged K/V pool has no layer at all (serve/kv_pool.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_ddp.models.hybrid import rms_norm
from tpu_ddp.models.transformer import rope

RETENTION = "power_retention"
HIGHEST = lax.Precision.HIGHEST

# What one part of a prefill chunk may hold as float32 features (the
# queries' phi for the carry, the keys' phi for the state update): half
# of the v5e's 128 MiB of on-chip memory, the bound serve/engine.py's
# attention keeps its scores within. At Brumby-14B's widths a chunk of
# 512 is 16 parts of 32 queries (40 heads x 8,256 features, 42 MB) and
# 4 parts of 128 keys (8 heads, 34 MB): a chunk's whole phi(Q) would be
# 676 MB and its phi(K) 135 MB.
PART_BYTES = 64 << 20

# The gate's biases spread the K/V heads' decays from 1 - 2^-6 to
# 1 - 2^-13 at a zero input: memories of 64 to 8,192 positions.
DECAY_BITS = (6, 13)


def features(d: int) -> int:
    """The symmetric power's width at a head of ``d``."""
    return d * (d + 1) // 2


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@dataclasses.dataclass(frozen=True)
class RetentionLM:
    name: str = "RetentionLM"
    vocab_size: int = 512
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim_: int = 16
    d_model: int = 64
    d_ff: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    retention_eps: float = 1e-6
    max_seq_len: int = 128
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # decode.mlp: a SwiGLU MLP, no expert
    swiglu = True
    moe_experts = 0
    # the decode path serves dense single-device models (check_decodable)
    sp_axis = tp_axis = ep_axis = None

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")

    # ---- what the decode core asks of a model --------------------------

    @property
    def mixers(self) -> tuple:
        """Every layer keeps a fixed-size retention state, no K/V."""
        return (RETENTION,) * self.num_layers

    @property
    def head_dim(self) -> int:
        return self.head_dim_

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def feature_dim(self) -> int:
        return features(self.head_dim)

    def state_shapes(self, num_slots: int) -> dict:
        """Per layer and slot, ``S`` (KV, F, hd) and ``z`` (KV, F) in
        float32."""
        lead = (self.num_layers, num_slots, self.kv_heads, self.feature_dim)
        dt = jnp.dtype(jnp.float32)
        return {"S": jax.ShapeDtypeStruct(lead + (self.head_dim,), dt),
                "z": jax.ShapeDtypeStruct(lead, dt)}

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.norm_eps)

    def qkv_proj(self, blk, y, pos):
        """q (B, L, H, hd) and k / v (B, L, KV, hd) from normalised
        ``y``: q and k each normalised per head, then turned at
        ``pos``; q is not scaled here (see :func:`retention_step`)."""
        cd = self.compute_dtype

        def normed(w, scale):
            a = jnp.einsum("bld,dhk->blhk", y, w.astype(cd),
                           preferred_element_type=jnp.float32)
            return rope(rms_norm(a, scale, self.norm_eps), pos,
                        self.rope_theta).astype(cd)

        q = normed(blk["wq"], blk["q_norm"]["scale"])
        k = normed(blk["wkv"][:, 0], blk["k_norm"]["scale"])
        v = jnp.einsum("bld,dhk->blhk", y, blk["wkv"][:, 1].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        return q, k, v

    def gate(self, blk, y):
        """The log decay ``a`` (B, L, KV) of normalised ``y``, float32."""
        g = jnp.einsum("bld,dg->blg", y, blk["w_gate"].astype(
            self.compute_dtype), preferred_element_type=jnp.float32)
        return jax.nn.log_sigmoid(g + blk["b_gate"].astype(jnp.float32))

    def out_proj(self, blk, o):
        """(B, L, H, hd) -> (B, L, dm) in the compute dtype."""
        cd = self.compute_dtype
        return jnp.einsum("blhk,hkd->bld", o.astype(cd), blk["wo"].astype(cd),
                          preferred_element_type=jnp.float32).astype(cd)

    def embed(self, params, tokens):
        return params["embed"][tokens].astype(self.compute_dtype)

    def head_apply(self, params, x):
        """Final RMSNorm and the untied head: (B, L, dm) -> (B, L, V)
        float32."""
        with jax.named_scope("head"):
            x = self.norm(x, params["ln_f"])
            return jnp.einsum("bld,dv->blv", x,
                              params["head"].astype(self.compute_dtype),
                              preferred_element_type=jnp.float32)

    # ---- parameters ----------------------------------------------------

    def gate_bias(self):
        """The gate's bias: K/V head ``g`` decays by ``1 - 2^-b_g`` at a
        zero input, ``b`` evenly over ``DECAY_BITS`` across the heads (as
        RetNet spreads its heads' memories), so that some heads carry
        thousands of positions."""
        lo, hi = DECAY_BITS
        bits = np.linspace(lo, hi, self.kv_heads)
        return jnp.asarray(np.log(2.0 ** bits - 1.0), jnp.float32)

    def init(self, key) -> dict:
        """Seeded random weights. Every matrix is normal with std
        ``c / sqrt(fan_in)``: ``c`` 1 for q, k, v and the MLP's gate and
        up projections, 0.5 for the matrices that write the residual
        stream (``wo``, ``w2``), 0.1 for the retention gate (so that its
        input moves each head's decay about its bias, not across the
        spread). Norm scales 1; the gate's bias :meth:`gate_bias`. The
        embedding std 1, the head std ``1 / sqrt(d_model)``."""
        pd = self.param_dtype
        dm, hd = self.d_model, self.head_dim
        fan = 1.0 / math.sqrt(dm)
        ones = lambda n: {"scale": jnp.ones((n,), pd)}  # noqa: E731
        keys = jax.random.split(key, self.num_layers + 2)
        blocks = []
        for k in keys[2:]:
            ks = jax.random.split(k, 6)
            blocks.append({
                "ln1": ones(dm), "ln2": ones(dm),
                "wq": _normal(ks[0], (dm, self.num_heads, hd), fan, pd),
                "wkv": _normal(ks[1], (dm, 2, self.kv_heads, hd), fan, pd),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "wo": _normal(ks[2], (self.num_heads, hd, dm),
                              0.5 / math.sqrt(self.num_heads * hd), pd),
                "w_gate": _normal(ks[3], (dm, self.kv_heads), 0.1 * fan, pd),
                "b_gate": self.gate_bias().astype(pd),
                "w1": _normal(ks[4], (dm, 2 * self.d_ff), fan, pd),
                "w2": _normal(ks[5], (self.d_ff, dm),
                              0.5 / math.sqrt(self.d_ff), pd),
            })
        return {"embed": _normal(keys[0], (self.vocab_size, dm), 1.0, pd),
                "head": _normal(keys[1], (dm, self.vocab_size), fan, pd),
                "ln_f": ones(dm), "blocks": tuple(blocks)}


# ---- the mixer -----------------------------------------------------------------

def sympow2(u):
    """phi(u): (..., d) -> (..., d (d + 1) / 2) float32, the products
    ``u_i u_j`` for ``i <= j`` in the order of the rows of ``i`` (row
    ``i`` holds ``j = i..d-1``), each off the diagonal times sqrt 2, so
    that ``phi(u) . phi(w) = (u . w)^2``. Built from static slices, no
    gather."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    off = u * math.sqrt(2.0)
    rows = [u[..., i:i + 1] * jnp.concatenate(
        [u[..., i:i + 1], off[..., i + 1:]], axis=-1) for i in range(d)]
    return jnp.concatenate(rows, axis=-1)


def _grouped(q, kv_heads: int):
    """(..., H, hd) -> (..., KV, G, hd): the queries of each K/V head."""
    return q.reshape(q.shape[:-2] + (kv_heads, -1, q.shape[-1]))


def retention_step(model, q, k, v, a, S, z):
    """One token for each of B sequences from their state: ``q`` (B, H,
    hd), ``k`` / ``v`` (B, KV, hd) in the compute dtype, ``a`` (B, KV) the
    log decay, ``S`` (B, KV, F, hd) and ``z`` (B, KV, F) the state before
    the token. The output is read out of that state,
    ``(exp(a) phi(q)^T S + (q . k)^2 v) / (exp(a) phi(q)^T z + (q . k)^2 +
    eps)``, which is ``phi(q)^T S_new / (phi(q)^T z_new + eps)``, so the
    readout does not wait for the update. Returns (o (B, H, hd) float32,
    S_new, z_new) in float32, whatever the dtype ``S`` and ``z`` come in."""
    scale = 1.0 / model.head_dim          # phi(q / sqrt(hd)) = phi(q) / hd
    qg = _grouped(q, model.kv_heads)                       # (B, KV, G, hd)
    decay = jnp.exp(a)                                     # (B, KV)
    S32, z32 = S.astype(jnp.float32), z.astype(jnp.float32)
    phiq = sympow2(qg) * scale                             # (B, KV, G, F)
    phik = sympow2(k)                                      # (B, KV, F)
    own = jnp.einsum("bgqd,bgd->bgq", qg, k,
                     preferred_element_type=jnp.float32) ** 2 * scale
    v32 = v.astype(jnp.float32)
    num = decay[..., None, None] * jnp.einsum(
        "bgqf,bgfd->bgqd", phiq, S32, precision=HIGHEST) \
        + own[..., None] * v32[:, :, None]
    den = decay[..., None] * jnp.einsum("bgqf,bgf->bgq", phiq, z32,
                                        precision=HIGHEST) + own
    o = num / (den[..., None] + model.retention_eps)
    S_new = decay[..., None, None] * S32 + phik[..., None] * v32[:, :, None]
    z_new = decay[..., None] * z32 + phik
    return o.reshape(q.shape), S_new, z_new


def _parts(n: int, row_bytes: int) -> int:
    """Into how many equal parts ``n`` rows of ``row_bytes`` each are cut
    so that a part stays within ``PART_BYTES``: halved while it does not
    and ``n`` allows."""
    parts = 1
    while n * row_bytes > parts * PART_BYTES and n % (2 * parts) == 0:
        parts *= 2
    return parts


def retention_chunk(model, q, k, v, a, S0, z0):
    """A run of C positions of ONE sequence from its incoming state:
    ``q`` (C, H, hd), ``k`` / ``v`` (C, KV, hd), ``a`` (C, KV); ``S0``
    (KV, F, hd), ``z0`` (KV, F). A padding row must not advance the state:
    its ``a`` is 0 (gate 1) and its ``k`` and ``v`` are zero, which the
    caller sees to. With ``A_t`` the sum of ``a`` over the run up to
    ``t``::

        num_t = exp(A_t) phi(q_t)^T S0 + sum_{s<=t} (q_t.k_s)^2 exp(A_t - A_s) v_s
        den_t = exp(A_t) phi(q_t)^T z0 + sum_{s<=t} (q_t.k_s)^2 exp(A_t - A_s)
        S     = exp(A_end) S0 + sum_s exp(A_end - A_s) phi(k_s) v_s^T

    (every exponent <= 0). Under the scope ``chunk`` the quadratic part;
    under ``carry`` what reads or makes the state, in parts of queries and
    of keys whose float32 features stay within ``PART_BYTES``. Returns
    (o (C, H, hd) float32, S, z) in float32; ``o`` of a padding row is
    meaningless."""
    C, H, hd = q.shape
    KV = model.kv_heads
    F = model.feature_dim
    scale = 1.0 / hd
    qg = _grouped(q, KV)                                   # (C, KV, G, hd)
    A = jnp.cumsum(a.astype(jnp.float32), axis=0)          # (C, KV)
    v32 = v.astype(jnp.float32)
    with jax.named_scope("chunk"):
        w = jnp.einsum("tgqd,sgd->gqts", qg, k,
                       preferred_element_type=jnp.float32) ** 2 * scale
        seg = (A[:, None, :] - A[None, :, :]).transpose(2, 0, 1)  # (g, t, s)
        causal = jnp.tril(jnp.ones((C, C), bool))
        w = w * jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)),
                          0.0)[:, None]
        num = jnp.einsum("gqts,sgd->tgqd", w, v32, precision=HIGHEST)
        den = jnp.sum(w, axis=-1).transpose(2, 0, 1)       # (t, g, q)
    with jax.named_scope("carry"):
        S0, z0 = S0.astype(jnp.float32), z0.astype(jnp.float32)
        n = C // _parts(C, 4 * H * F)

        def read(part):
            qp, ap = part
            phiq = sympow2(qp) * scale                     # (n, KV, G, F)
            e = jnp.exp(ap)[..., None]                     # (n, KV, 1)
            return (e[..., None] * jnp.einsum("tgqf,gfd->tgqd", phiq, S0,
                                              precision=HIGHEST),
                    e * jnp.einsum("tgqf,gf->tgq", phiq, z0,
                                   precision=HIGHEST))

        cn, cd = lax.map(read, (qg.reshape((C // n, n) + qg.shape[1:]),
                                A.reshape(C // n, n, KV)))
        num = num + cn.reshape(num.shape)
        den = den + cd.reshape(den.shape)
        left = jnp.exp(A[-1][None] - A)                    # (C, KV)
        m = C // _parts(C, 4 * KV * F)

        def fold(carry, part):
            kp, vp, lp = part
            phik = sympow2(kp) * lp[..., None]             # (m, KV, F)
            return (carry[0] + jnp.einsum("sgf,sgd->gfd", phik, vp,
                                          precision=HIGHEST),
                    carry[1] + jnp.sum(phik, axis=0)), None

        ends = jnp.exp(A[-1])                              # (KV,)
        (S, z), _ = lax.scan(
            fold, (ends[:, None, None] * S0, ends[:, None] * z0),
            (k.reshape((C // m, m) + k.shape[1:]),
             v32.reshape((C // m, m) + v.shape[1:]),
             left.reshape(C // m, m, KV)))
    o = num / (den[..., None] + model.retention_eps)
    return o.reshape(C, H, hd), S, z


__all__ = ["DECAY_BITS", "PART_BYTES", "RETENTION", "RetentionLM", "features",
           "retention_chunk", "retention_step", "sympow2"]
