"""``SparseMoeLM``: a decoder whose attention reads a LEARNED selection of
the cached positions, followed in every layer by a dropless top-k expert
layer with no shared expert. The Qwen3-MoE block (RMSNorm, per-head q/k
RMSNorm, rotate-half RoPE, GQA, an untied head) with a DeepSeek-Sparse-
Attention lightning indexer on top (Keye-VL-2.0's ``sa_config``).

Per layer, for the query at position ``t`` (``h = RMSNorm(x)``):

    qI_j = RoPE_r(h WIq_j)                 j = 1..J index heads of Di
    kI   = RoPE_r(LayerNorm(h WIk))        one index key of Di, cached
    w_j  = (h Ww)_j / sqrt(J)
    I[t, s] = sum_j w_j ReLU(qI_j . kI[s]) / sqrt(Di)        s <= t
    S_t  = the min(t + 1, topk) positions s <= t of largest I[t, s]
    o    = GQA attention of q[t] over the K/V of S_t only

``RoPE_r`` rotates the first ``index_rope_dim`` dimensions and leaves the
rest. Ties at the cutoff go to the lower position (``lax.top_k``'s
order). At a length of at most ``topk`` every position is selected and
the mixer is dense attention.

Serving only: the model offers what the decode core
(tpu_ddp/models/decode.py) and ``ServeEngine`` ask of a model, as
``HybridLM`` does, and ``index_dim``: the width of the index key each
layer caches per position beside its K/V, in a pool of its own indexed
by the same block tables (serve/kv_pool.py ``PagedKVPool.index``). The
two forms of the mixer (a decode step's gather of the selected rows, a
prefill chunk's mask over the gathered view) live in serve/engine.py;
the scoring and the selection are :func:`index_scores`,
:func:`select_top` and :func:`selection_mask` here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from tpu_ddp.models.hybrid import rms_norm
from tpu_ddp.models.transformer import layer_norm, rope


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rope_part(x, positions, base: float, dims: int):
    """Rotate-half RoPE on the first ``dims`` of the last axis of ``x``
    (..., L, H, D); the rest passes through."""
    if dims == x.shape[-1]:
        return rope(x, positions, base)
    return jnp.concatenate(
        [rope(x[..., :dims], positions, base), x[..., dims:]], axis=-1)


@dataclasses.dataclass(frozen=True)
class SparseMoeLM:
    name: str = "SparseMoeLM"
    vocab_size: int = 512
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim_: int = 16
    d_model: int = 64
    d_ff: int = 32              # one expert's width
    num_experts: int = 16       # the router's width
    top_k: int = 4
    held: tuple | None = None   # [lo, hi) of the experts held; None: all
    index_heads: int = 4
    index_head_dim: int = 8
    index_rope_dim: int = 4
    index_topk: int = 16
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    max_seq_len: int = 128
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # no shared expert: decode.mlp adds none
    shared_ff = 0
    # the decode path serves dense single-device models (check_decodable)
    sp_axis = tp_axis = ep_axis = None

    def __post_init__(self):
        if self.held is None:
            object.__setattr__(self, "held", (0, self.num_experts))
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k={self.top_k} of {self.num_experts}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if not 0 < self.index_rope_dim <= self.index_head_dim \
                or self.index_rope_dim % 2:
            raise ValueError("index_rope_dim must be even and within "
                             "index_head_dim")

    # ---- what the decode core asks of a model --------------------------

    @property
    def mixers(self) -> tuple:
        """Every layer: attention over a learned selection of its cached
        positions (K/V pages and index-key pages)."""
        return ("sparse_attention",) * self.num_layers

    @property
    def head_dim(self) -> int:
        return self.head_dim_

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def moe_experts(self) -> int:
        return self.num_experts

    @property
    def index_dim(self) -> int:
        """The index key each layer caches per position."""
        return self.index_head_dim

    def state_shapes(self, num_slots: int) -> dict:
        return {}

    def norm(self, x, p):
        return rms_norm(x, p["scale"], self.norm_eps)

    def qkv_proj(self, blk, y, pos):
        """q (B, L, H, hd) and k/v (B, L, KV, hd) from normalised ``y``:
        q and k each normalised per head, then rotated at ``pos``."""
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

    def index_proj(self, blk, y, pos):
        """The indexer's side of normalised ``y`` (B, L, dm) at ``pos``:
        queries (B, L, J, Di) and the key to cache (B, L, Di) in the
        compute dtype, the head weights (B, L, J) in float32."""
        cd = self.compute_dtype
        r = self.index_rope_dim
        qi = jnp.einsum("bld,djk->bljk", y, blk["index_wq"].astype(cd),
                        preferred_element_type=jnp.float32)
        qi = rope_part(qi, pos, self.rope_theta, r).astype(cd)
        ki = jnp.einsum("bld,dk->blk", y, blk["index_wk"].astype(cd),
                        preferred_element_type=jnp.float32)
        ki = layer_norm(ki, blk["index_norm"]["scale"].astype(jnp.float32),
                        blk["index_norm"]["bias"].astype(jnp.float32),
                        self.norm_eps)
        ki = rope_part(ki[..., None, :], pos, self.rope_theta,
                       r)[..., 0, :].astype(cd)
        w = jnp.einsum("bld,dj->blj", y, blk["index_ww"].astype(cd),
                       preferred_element_type=jnp.float32)
        return qi, ki, w / math.sqrt(self.index_heads)

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

    def init(self, key) -> dict:
        """Seeded random weights. Every matrix is normal with std
        ``c / sqrt(fan_in)``: ``c`` 1 for the q, k, v, index and router
        projections (each output spreads about 1 at any width), 0.5 for
        the matrices that write the residual stream (``wo``, ``w2``) and
        the experts' ``w1``. Every norm scale starts at 1, the per-head q
        / k norms too (so that attention logits spread about 1), the
        index LayerNorm's bias at 0. The embedding and the head normal
        with std 1 and ``1 / sqrt(d_model)``."""
        pd = self.param_dtype
        dm, hd, di = self.d_model, self.head_dim, self.index_head_dim
        lo, hi = self.held
        fan = 1.0 / math.sqrt(dm)
        ones = lambda n: {"scale": jnp.ones((n,), pd)}  # noqa: E731
        keys = jax.random.split(key, self.num_layers + 2)
        blocks = []
        for k in keys[2:]:
            ks = jax.random.split(k, 10)
            blocks.append({
                "ln1": ones(dm), "ln2": ones(dm),
                "wq": _normal(ks[0], (dm, self.num_heads, hd), fan, pd),
                "wkv": _normal(ks[1], (dm, 2, self.kv_heads, hd), fan, pd),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "wo": _normal(ks[2], (self.num_heads, hd, dm),
                              0.5 / math.sqrt(self.num_heads * hd), pd),
                "index_wq": _normal(ks[3], (dm, self.index_heads, di), fan,
                                    pd),
                "index_wk": _normal(ks[4], (dm, di), fan, pd),
                "index_norm": {"scale": jnp.ones((di,), pd),
                               "bias": jnp.zeros((di,), pd)},
                "index_ww": _normal(ks[5], (dm, self.index_heads), fan, pd),
                "router": _normal(ks[6], (dm, self.num_experts), fan, pd),
                "w1": _normal(ks[7], (hi - lo, dm, 2 * self.d_ff),
                              0.5 * fan, pd),
                "w2": _normal(ks[8], (hi - lo, self.d_ff, dm),
                              0.5 / math.sqrt(self.d_ff), pd),
            })
        return {"embed": _normal(keys[0], (self.vocab_size, dm), 1.0, pd),
                "head": _normal(keys[1], (dm, self.vocab_size), fan, pd),
                "ln_f": ones(dm), "blocks": tuple(blocks)}


# ---- the indexer and the selection ---------------------------------------------

def index_scores(model, qi, w, keys):
    """``I[b, q, s] = sum_j w[b, q, j] ReLU(qi[b, q, j] . kI[b, s]) /
    sqrt(Di)`` in float32: ``qi`` (B, Q, J, Di), ``w`` (B, Q, J) ->
    (B, Q, T). ``keys`` (B, N, W) holds the index keys of positions
    ``0..T-1`` in order, ``P = W // Di`` of them side by side in a row
    (the pool's pages, serve/kv_pool.py ``index_page``; ``P`` 1 for one
    key a row): each query is set against a row once, as ``P`` copies of
    itself, each copy zero outside its own key's lanes, so the pages are
    read in their own layout."""
    di = model.index_head_dim
    _, n, width = keys.shape
    per_row = width // di
    if per_row == 1:
        dots = jnp.einsum("bqjd,bnd->bqjn", qi, keys,
                          preferred_element_type=jnp.float32)
        s = jnp.einsum("bqjn,bqj->bqn", jax.nn.relu(dots), w)
    else:
        spread = jnp.einsum("bqjd,pr->bqjprd", qi,
                            jnp.eye(per_row, dtype=qi.dtype))
        spread = spread.reshape(spread.shape[:4] + (width,))
        dots = jnp.einsum("bqjpw,bnw->bqjnp", spread, keys,
                          preferred_element_type=jnp.float32)
        s = jnp.einsum("bqjnp,bqj->bqnp", jax.nn.relu(dots), w)
        s = s.reshape(s.shape[:2] + (n * per_row,))
    return s / math.sqrt(di)


def _masked(scores, q_pos):
    """Scores of positions past each query's own (``q_pos`` (B, Q))
    pushed below every real score. A zero score is made +0, so that -0
    and +0 are one value to the selection as they are to ``==``."""
    t = jnp.arange(scores.shape[-1])
    return jnp.where(t[None, None, :] <= q_pos[..., None],
                     jnp.where(scores == 0, 0.0, scores), -jnp.inf)


def _order_key(x):
    """float32 -> uint32 in the same order (-inf lowest)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def top_mask(scores, k: int):
    """A (..., T) mask of the ``k`` largest of ``scores`` (..., T), ties
    to the lower position: exactly ``k`` true in a row. No sort: the
    ``k``-th largest value is found a bit at a time (32 counts over the
    row), then the position up to which the values equal to it are kept
    (one count a bit of a position). A sort of 36,864 scores was most of
    a prefill chunk's time at Keye-VL-2.0's widths."""
    u = _order_key(scores)
    lead = u.shape[:-1]

    def value_bit(i, cut):
        cand = cut | jax.lax.shift_left(jnp.uint32(1),
                                        (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[..., None], axis=-1)
        return jnp.where(n >= k, cand, cut)

    cut = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(lead, jnp.uint32))
    above = u > cut[..., None]
    tied = u == cut[..., None]
    need = k - jnp.sum(above, axis=-1)
    t = jnp.arange(u.shape[-1], dtype=jnp.int32)
    bits = max(1, (u.shape[-1] - 1).bit_length())

    def position_bit(i, last):
        cand = last | jax.lax.shift_left(1, bits - 1 - i)
        n = jnp.sum(tied & (t < cand[..., None]), axis=-1)
        return jnp.where(n < need, cand, last)

    last = jax.lax.fori_loop(0, bits, position_bit,
                             jnp.zeros(lead, jnp.int32))
    return above | (tied & (t <= last[..., None]))


GROUP = 128     # positions whose ranks one product counts


def mask_positions(mask, k: int):
    """The ``k`` true positions of each row of ``mask`` (..., T), in
    ascending order: (..., k) int32. Counted in groups of ``GROUP`` by
    products of 0/1 matrices (exact: every count is at most ``GROUP``),
    with no sort, gather or scatter: a group's ranks, the group each
    output falls in, then the lane within it."""
    lead, T = mask.shape[:-1], mask.shape[-1]
    groups = -(-T // GROUP)
    m = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, groups * GROUP - T)])
    m = m.reshape(lead + (groups, GROUP)).astype(jnp.bfloat16)
    lane = jnp.arange(GROUP)
    upper = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)
    ranks = jnp.einsum("...gl,lh->...gh", m, upper,
                       preferred_element_type=jnp.float32)
    counts = ranks[..., -1]
    starts = jnp.cumsum(counts, axis=-1) - counts            # (..., G)
    out = jnp.arange(k, dtype=jnp.float32)
    before = starts[..., None, :] <= out[:, None]             # (..., k, G)
    group = jnp.sum(before, axis=-1) - 1
    start = jnp.max(jnp.where(before, starts[..., None, :], -1.0), axis=-1)
    pick = (jnp.arange(groups) == group[..., None]).astype(jnp.bfloat16)
    rows = jnp.einsum("...kg,...gh->...kh", pick,
                      ranks.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    within = jnp.sum(rows <= (out - start)[..., None], axis=-1)
    return (group * GROUP + within).astype(jnp.int32)


def select_top(model, scores, q_pos):
    """The positions each query keeps: ``(pos, keep)``, both (B, Q, K)
    with ``K = min(topk, T)``, ascending: the ``K`` largest scores at
    positions <= the query's, ties to the lower position; ``keep`` is
    false for the padding a query with fewer than ``K`` positions gets."""
    k = min(model.index_topk, scores.shape[-1])
    pos = mask_positions(top_mask(_masked(scores, q_pos), k), k)
    return pos, pos <= q_pos[..., None]


def selection_mask(model, scores, q_pos):
    """The same selection as :func:`select_top`, as a (B, Q, T) mask over
    the positions."""
    k = min(model.index_topk, scores.shape[-1])
    t = jnp.arange(scores.shape[-1])
    return top_mask(_masked(scores, q_pos), k) & (t <= q_pos[..., None])


__all__ = ["SparseMoeLM", "index_scores", "mask_positions",
           "rope_part", "select_top", "selection_mask", "top_mask"]
