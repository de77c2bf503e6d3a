"""``HybridLM``: a decoder whose layers choose between two mixers, causal
attention (K/V that grows with the sequence) and a Mamba-2 state-space
mixer (a fixed-size recurrent state per sequence), each followed by a
dropless top-k expert layer plus a shared gated MLP. RMSNorm, no bias, no
positional encoding, a tied head, and the four multipliers of the
``granitemoehybrid`` family (embedding, residual, attention, logits).

Serving only: the model offers what the decode core
(tpu_ddp/models/decode.py) and ``ServeEngine`` ask of a model —
``init``, ``head_apply``, the dtypes, ``kv_heads`` / ``head_dim`` /
``attn_scale``, ``mixers`` (what each layer keeps: K/V pages or recurrent
state), ``state_shapes`` and ``held`` (which experts of each layer's
``num_experts`` this chip holds; the router keeps its full width and the
chip computes its own experts' part of the sum). There is no training
path: the backward of the chunked scan does not exist yet.

The Mamba-2 mixer has two forms of one recurrence (per head, state ``S``
(head_dim, N): ``S_t = exp(dt_t A) S_{t-1} + dt_t outer(x_t, B_t)``,
``y_t = S_t C_t + D x_t``): :func:`ssm_step`, one token for a bank of
sequences, and :func:`ssm_chunk`, a run of tokens of one sequence from its
incoming state by the chunked (state-space dual) form, ``ssm_chunk``
positions at a time. The plain reference
(benchmark/reference/granite_hybrid.py) scans one position at a time.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

ATTENTION, MAMBA = "attention", "mamba"


def rms_norm(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@dataclasses.dataclass(frozen=True)
class HybridLM:
    name: str = "HybridLM"
    vocab_size: int = 1024
    layer_types: tuple = (MAMBA, MAMBA, ATTENTION, MAMBA)
    num_heads: int = 4
    num_kv_heads: int = 2
    d_model: int = 64
    d_ff: int = 32              # one expert's width
    shared_ff: int = 64
    num_experts: int = 8        # the router's width
    top_k: int = 3
    held: tuple | None = None   # [lo, hi) of the experts held; None: all
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_groups: int = 1
    ssm_chunk: int = 8
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None   # None: 1/sqrt(head_dim)
    logits_scaling: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # the decode path serves dense single-device models (check_decodable)
    sp_axis = tp_axis = ep_axis = None

    def __post_init__(self):
        if set(self.layer_types) - {ATTENTION, MAMBA}:
            raise ValueError(f"layer_types {self.layer_types}: expected "
                             f"{ATTENTION!r} or {MAMBA!r}")
        if self.held is None:
            object.__setattr__(self, "held", (0, self.num_experts))
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} of {self.num_experts}")
        if self.num_heads % self.num_kv_heads \
                or self.d_model % self.num_heads \
                or self.ssm_heads % self.ssm_groups:
            raise ValueError("heads must divide: num_heads by "
                             "num_kv_heads, d_model by num_heads, "
                             "ssm_heads by ssm_groups")

    # ---- what the decode core asks of a model --------------------------

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def mixers(self) -> tuple:
        """Per layer, what it keeps: ``"attention"`` K/V pages, or
        ``"mamba"`` recurrent state."""
        return self.layer_types

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def attn_scale(self) -> float:
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        return 1.0 / (self.head_dim ** 0.5)

    @property
    def moe_experts(self) -> int:
        return self.num_experts

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def state_shapes(self, num_slots: int) -> dict:
        """The state pool of ``num_slots`` sequences: per state layer
        and slot, the recurrence's ``S`` in float32 (what the reference
        computes in; narrower is another result, not a faster one) and
        the convolution's tail (the last ``ssm_conv - 1`` inputs) in the
        compute dtype, which its input is computed in."""
        n = sum(m == MAMBA for m in self.layer_types)
        return {
            "ssm": jax.ShapeDtypeStruct(
                (n, num_slots, self.ssm_heads, self.ssm_head_dim,
                 self.ssm_state), jnp.dtype(jnp.float32)),
            "conv": jax.ShapeDtypeStruct(
                (n, num_slots, self.ssm_conv - 1, self.conv_dim),
                jnp.dtype(self.compute_dtype)),
        }

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.norm_eps)

    def qkv_proj(self, blk, y, pos):
        """q (B, L, H, hd) and k/v (B, L, KV, hd) from normalised ``y``;
        ``pos`` is unused: the family has no positional encoding."""
        cd = self.compute_dtype
        q = jnp.einsum("bld,dhk->blhk", y, blk["wq"].astype(cd),
                       preferred_element_type=jnp.float32).astype(cd)
        kv = jnp.einsum("bld,dcgk->blcgk", y, blk["wkv"].astype(cd),
                        preferred_element_type=jnp.float32).astype(cd)
        return q, kv[:, :, 0], kv[:, :, 1]

    def embed(self, params, tokens):
        x = params["embed"][tokens].astype(self.compute_dtype)
        return x * jnp.asarray(self.embedding_multiplier, x.dtype)

    def head_apply(self, params, x):
        """Final RMSNorm and the tied head: (B, L, dm) -> (B, L, V)
        float32, divided by ``logits_scaling``."""
        with jax.named_scope("head"):
            x = self.norm(x, params["ln_f"])
            logits = jnp.einsum(
                "bld,vd->blv", x,
                params["embed"].astype(self.compute_dtype),
                preferred_element_type=jnp.float32)
            return logits / self.logits_scaling

    # ---- parameters ----------------------------------------------------

    def init(self, key) -> dict:
        """Seeded random weights. Matrices that read the normalised
        stream are scaled by their fan-in, so that what they give has the
        same spread at any width: ``in_proj``, ``w1``, ``shared_w1`` at
        std ``1.28 / sqrt(d_model)`` (0.02 at 4096), which keeps ``dt``
        in Mamba-2's own range; ``wq`` / ``wkv`` at ``4.8 /
        sqrt(d_model)`` (0.075), so that scores times a small
        ``attention_multiplier`` still spread; the router at ``3.2 /
        sqrt(d_model)`` (0.05). Matrices that write the residual stream
        and the tied embedding at 0.1, so that the layers outweigh
        ``embedding_multiplier`` times the embedding and the tied head
        does not simply name its input. ``A`` uniform in 1-16, ``dt``
        log-uniform in 0.001-0.1 through the inverse softplus, ``D`` = 1
        (Mamba-2's own); the convolution uniform in +-1/sqrt(K)."""
        pd = self.param_dtype
        dm, hd = self.d_model, self.head_dim
        lo, hi = self.held
        read, qkv, route = (c / math.sqrt(dm) for c in (1.28, 4.8, 3.2))
        ones = lambda n: {"scale": jnp.ones((n,), pd)}  # noqa: E731
        keys = jax.random.split(key, self.num_layers + 1)
        blocks = []
        for kind, k in zip(self.layer_types, keys[1:]):
            ks = jax.random.split(k, 12)
            blk = {
                "ln1": ones(dm), "ln2": ones(dm),
                "router": _normal(ks[0], (dm, self.num_experts), route, pd),
                "w1": _normal(ks[1], (hi - lo, dm, 2 * self.d_ff), read,
                              pd),
                "w2": _normal(ks[2], (hi - lo, self.d_ff, dm), 0.1, pd),
                "shared_w1": _normal(ks[3], (dm, 2 * self.shared_ff),
                                     read, pd),
                "shared_w2": _normal(ks[4], (self.shared_ff, dm), 0.1, pd),
            }
            if kind == ATTENTION:
                blk.update(
                    wq=_normal(ks[5], (dm, self.num_heads, hd), qkv, pd),
                    wkv=_normal(ks[6], (dm, 2, self.kv_heads, hd), qkv,
                                pd),
                    wo=_normal(ks[7], (self.num_heads, hd, dm), 0.1, pd))
            else:
                di, cdim = self.ssm_inner, self.conv_dim
                dt = jnp.exp(jax.random.uniform(
                    ks[8], (self.ssm_heads,), jnp.float32,
                    math.log(1e-3), math.log(1e-1)))
                bound = 1.0 / math.sqrt(self.ssm_conv)
                blk.update(
                    in_proj=_normal(
                        ks[5], (dm, di + cdim + self.ssm_heads), read, pd),
                    conv_w=jax.random.uniform(
                        ks[6], (self.ssm_conv, cdim), jnp.float32,
                        -bound, bound).astype(pd),
                    conv_b=jax.random.uniform(
                        ks[7], (cdim,), jnp.float32, -bound,
                        bound).astype(pd),
                    dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(
                        jnp.float32),
                    A_log=jnp.log(jax.random.uniform(
                        ks[9], (self.ssm_heads,), jnp.float32, 1.0, 16.0)),
                    D=jnp.ones((self.ssm_heads,), jnp.float32),
                    norm=ones(di),
                    out_proj=_normal(ks[10], (di, dm), 0.1, pd))
            blocks.append(blk)
        return {"embed": _normal(keys[0], (self.vocab_size, dm), 0.1, pd),
                "ln_f": ones(dm), "blocks": tuple(blocks)}


# ---- the Mamba-2 mixer ------------------------------------------------------

def _project(model, blk, h):
    """``[z, xBC, dt] = h @ W_in``: (..., d_inner), (..., conv_dim) in
    the compute dtype, and dt (..., heads) in float32."""
    cd = model.compute_dtype
    zxbcdt = jnp.einsum("...d,de->...e", h, blk["in_proj"].astype(cd),
                        preferred_element_type=jnp.float32)
    di, cdim = model.ssm_inner, model.conv_dim
    z = zxbcdt[..., :di].astype(cd)
    xbc = zxbcdt[..., di:di + cdim].astype(cd)
    return z, xbc, zxbcdt[..., di + cdim:]


def _split_xbc(model, xbc):
    """x (..., heads, head_dim), B and C (..., groups, N) in float32, at
    the width of the ``ssm_groups`` groups that share them."""
    di, gn = model.ssm_inner, model.ssm_groups * model.ssm_state
    lead = xbc.shape[:-1]
    xbc = xbc.astype(jnp.float32)
    x = xbc[..., :di].reshape(lead + (model.ssm_heads, model.ssm_head_dim))
    b, c = (part.reshape(lead + (model.ssm_groups, model.ssm_state))
            for part in (xbc[..., di:di + gn], xbc[..., di + gn:]))
    return x, b, c


def _per_head(a, heads: int):
    """A group's (..., groups, N) repeated over its heads."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def _finish(model, blk, y, z):
    """Gate, norm over all of ``d_inner``, output projection."""
    cd = model.compute_dtype
    y = y.reshape(z.shape).astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y, blk["norm"]["scale"], model.norm_eps).astype(cd)
    return jnp.einsum("...e,ed->...d", y, blk["out_proj"].astype(cd),
                      preferred_element_type=jnp.float32).astype(cd)


def advance_state(ssm, decay, dtx, b, c):
    """The recurrence's one-token step, the plain body: ``ssm`` (S,
    heads, head_dim, N) times ``decay`` (S, heads) plus the outer product
    of ``dtx`` (S, heads, head_dim), the token's ``dt * x``, and ``b``;
    ``y = S c`` is read out of the NEW state. ``b`` / ``c`` (S, groups,
    N). Returns (y (S, heads, head_dim), ssm), float32. The definition
    that ops/pallas/ssm_state_step.py is tested against."""
    b, c = (_per_head(a, ssm.shape[1]) for a in (b, c))
    ssm = (decay[..., None, None] * ssm.astype(jnp.float32)
           + dtx[..., None] * b[..., None, :])
    return jnp.einsum("shpn,shn->shp", ssm, c), ssm


def ssm_step(model, blk, h, ssm, conv, advance=None):
    """One token for each of S sequences. ``h`` (S, dm) normalised
    input; ``ssm`` (S, heads, head_dim, N) and ``conv`` (S, K-1,
    conv_dim) the sequences' state. ``advance``: what steps the
    recurrence, :func:`advance_state` unless given: something with its
    signature and result that keeps the state elsewhere (the serve
    step's kernel over the pool; ``ssm`` is then whatever it takes and
    gives back). Returns (out (S, dm), ssm, conv)."""
    z, xbc, dt = _project(model, blk, h)
    window = jnp.concatenate([conv, xbc[:, None].astype(conv.dtype)],
                             axis=1)                       # (S, K, cdim)
    out = jnp.einsum("skc,kc->sc", window.astype(jnp.float32),
                     blk["conv_w"].astype(jnp.float32)) \
        + blk["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(out).astype(model.compute_dtype)
    x, b, c = _split_xbc(model, xbc)
    dt = jax.nn.softplus(dt + blk["dt_bias"])               # (S, heads)
    decay = jnp.exp(dt * -jnp.exp(blk["A_log"]))
    y, ssm = (advance or advance_state)(ssm, decay, dt[..., None] * x, b,
                                        c)
    y = y + blk["D"][:, None] * x
    return _finish(model, blk, y, z), ssm, window[:, 1:]


def ssm_chunk(model, blk, h, ssm, conv, n_valid):
    """A run of C tokens of ONE sequence from its incoming state.
    ``h`` (C, dm); ``ssm`` (heads, head_dim, N); ``conv`` (K-1, conv_dim);
    the first ``n_valid`` rows are the sequence's, the rest padding,
    which must not advance the state: a padding row has ``dt = 0``
    (decay 1, input 0), and the new tail is the last K-1 valid inputs.
    Computed ``ssm_chunk`` positions at a time by the chunked form.
    Returns (out (C, dm), ssm, conv); ``out`` of a padding row is
    meaningless."""
    C = h.shape[0]
    K = model.ssm_conv
    z, xbc, dt = _project(model, blk, h)
    padded = jnp.concatenate([conv, xbc.astype(conv.dtype)])  # (K-1+C, .)
    w = blk["conv_w"].astype(jnp.float32)
    out = sum(w[j] * padded[j:j + C].astype(jnp.float32)
              for j in range(K)) + blk["conv_b"].astype(jnp.float32)
    # row i of ``padded`` is input i - (K-1): the tail after n_valid
    # inputs is rows n_valid .. n_valid + K - 2
    tail = jax.lax.dynamic_slice_in_dim(padded, n_valid, K - 1, axis=0)
    x, b, c = _split_xbc(model, jax.nn.silu(out).astype(model.compute_dtype))
    b, c = (_per_head(a, model.ssm_heads) for a in (b, c))
    valid = jnp.arange(C) < n_valid
    dt = jnp.where(valid[:, None],
                   jax.nn.softplus(dt + blk["dt_bias"]), 0.0)  # (C, heads)
    a = dt * -jnp.exp(blk["A_log"])                         # log decay
    Q = model.ssm_chunk if C % model.ssm_chunk == 0 else C
    ys = []
    state = ssm.astype(jnp.float32)
    for i in range(0, C, Q):
        xq, bq, cq, dtq = x[i:i + Q], b[i:i + Q], c[i:i + Q], dt[i:i + Q]
        cs = jnp.cumsum(a[i:i + Q], axis=0)                 # (Q, heads)
        # within the chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) dt_s
        # (C_t . B_s) x_s
        seg = cs[:, None, :] - cs[None, :, :]               # (t, s, heads)
        causal = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        scores = jnp.einsum("thn,shn->tsh", cq, bq)
        y = jnp.einsum("tsh,shp->thp", scores * decay * dtq[None], xq)
        # from the incoming state: exp(cs_t) C_t . S
        y = y + jnp.einsum("thn,hpn->thp", cq, state) \
            * jnp.exp(cs)[..., None]
        ys.append(y + blk["D"][:, None] * xq)
        left = jnp.exp(cs[-1][None] - cs) * dtq             # (s, heads)
        state = jnp.exp(cs[-1])[:, None, None] * state \
            + jnp.einsum("sh,shp,shn->hpn", left, xq, bq)
    y = jnp.concatenate(ys) if len(ys) > 1 else ys[0]
    return _finish(model, blk, y, z), state, tail


__all__ = ["ATTENTION", "MAMBA", "HybridLM", "advance_state", "rms_norm",
           "ssm_chunk", "ssm_step"]
