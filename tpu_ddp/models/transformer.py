"""Decoder-only transformer LM — the long-context model family.

No reference counterpart (the reference ships only VGG,
part1/model.py:49-50); this family exists because long-context training is
first-class in this framework. Same conventions as the rest of the zoo:
functional (init/apply over a pytree), bf16 compute with f32 params and
f32 softmax/LN statistics, static config on a frozen dataclass.

Sequence parallelism: ``apply`` takes the LOCAL sequence chunk. When
``sp_axis``/``sp_size`` are configured (and apply runs inside a
``shard_map`` over that axis), attention runs as ring attention over the
``sp`` mesh axis (tpu_ddp/parallel/ring_attention.py) and RoPE positions
are offset by the chunk's global start — so the model computes EXACTLY the
same function as the single-device configuration (tested in
tests/test_ring_attention.py).

Tensor parallelism: when ``tp_axis``/``tp_size`` are configured, each
block's parameters arrive as mp-shards (attention heads and the MLP hidden
axis split over ``tp_size`` — :meth:`TransformerLM.param_specs` is the
authoritative layout) and the block computes with the Megatron column/row
sandwich (tpu_ddp/parallel/tensor_parallel.py): two ``psum``s per block,
everything else replicated. Composes with sequence parallelism — ring
attention rotates K/V over ``sp`` within each head shard.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from tpu_ddp.parallel.ring_attention import attend
from tpu_ddp.parallel.tensor_parallel import tp_input, tp_output


def _normal(key, shape, std, dtype):
    return std * jax.random.normal(key, shape, dtype)


def rope(x, positions, base: float = 10000.0):
    """Rotary position embedding. x: (B, L, H, D); positions: (L,)
    shared across the batch (training / offline decode), or (B, L)
    per-row (continuous-batching decode, where every live sequence
    sits at its own offset — tpu_ddp/serve/). The (L,) path is
    bit-identical to the original shared-position formulation."""
    d = x.shape[-1]
    half = d // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]  # (..., L, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    if angles.ndim == 2:  # shared (L,) positions: add the batch dim
        cos, sin = cos[None], sin[None]
    x1, x2 = x[..., :half], x[..., half:]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin],
        axis=-1).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class TransformerLM:
    """GPT-style pre-LN decoder. Causal by construction."""

    name: str = "TransformerLM"
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    # Grouped-query attention (Ainslie et al., arXiv:2305.13245): K/V get
    # ``num_kv_heads`` heads shared by groups of Q heads. None -> MHA
    # (= num_heads; the "wqkv" param layout is kept bit-compatible).
    # num_kv_heads=1 is multi-query attention. The KV cache shrinks by
    # num_heads/num_kv_heads (models/generate.py init_cache).
    num_kv_heads: int | None = None
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 2048
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # Sequence parallelism: mesh axis name/extent the LOCAL chunk lives on.
    # ``sp_mode`` picks the scheme: "ring" (K/V rotation via ppermute,
    # tpu_ddp/parallel/ring_attention.py) or "ulysses" (all-to-all head
    # re-sharding, tpu_ddp/parallel/ulysses.py). Both are exact.
    sp_axis: str | None = None
    sp_size: int = 1
    sp_mode: str = "ring"
    # Tensor parallelism: mesh axis name/extent block params are sharded on.
    tp_axis: str | None = None
    tp_size: int = 1
    # Mixture of experts: when > 0 every block's MLP is a routed MoE
    # with this many experts (tpu_ddp/parallel/moe.py); top_k=1 is
    # Switch routing, top_k=2 the GShard scheme.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    # Expert parallelism: mesh axis name/extent the expert axis shards on.
    ep_axis: str | None = None
    ep_size: int = 1
    # Use the Pallas flash-attention kernel
    # (tpu_ddp/ops/pallas/flash_attention.py). Honored when attention is
    # local: sp==1, or sp>1 with sp_mode="ulysses" (the kernel runs on
    # the all-to-all-gathered sequence). The ring path (sp>1, "ring")
    # has its own blockwise online softmax and ignores this flag.
    use_flash: bool = False
    # Memory policy (tpu_ddp/memory/policy.py): "blocks" remats each
    # transformer block in the backward pass — trades ~num_layers x
    # activation memory for one extra forward, the standard
    # long-context memory lever on HBM-bound chips; "dots" saves the
    # matmul outputs and recomputes LN/softmax/GELU ("conv_stages"
    # degrades to "blocks" here — no conv stages). act_dtype is the
    # saved dtype of the inter-block residual stream.
    remat: str = "none"
    act_dtype: str = "compute"
    # DEPRECATED alias for remat="blocks" (the pre-policy field); kept
    # functional for back-compat, ignored when ``remat`` is set.
    remat_blocks: bool = False
    # Dropout on the embedding and each block's two residual branches.
    # Active only when the caller passes an ``rng`` to apply/trunk (the
    # trainer does, per step); eval/generate never pass one, so they
    # are deterministic with no mode flag.
    dropout_rate: float = 0.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)

    @property
    def is_gqa(self) -> bool:
        return self.kv_heads != self.num_heads

    # What the decode core (models/decode.py) and the serving engine ask
    # of any model: its norm, its embedding, what each layer keeps
    # between steps, and the recurrent state of ``num_slots`` sequences
    # (none here: every layer's mixer is attention over cached K/V).

    @property
    def mixers(self) -> tuple:
        return ("attention",) * self.num_layers

    def state_shapes(self, num_slots: int) -> dict:
        return {}

    def norm(self, x, p):
        return layer_norm(x, p["scale"], p["bias"])

    def embed(self, params, tokens):
        return params["embed"][tokens].astype(self.compute_dtype)

    @property
    def remat_policy(self) -> str:
        """Effective remat mode, honoring the deprecated
        ``remat_blocks`` alias (``remat`` wins when set)."""
        if self.remat != "none":
            return self.remat
        return "blocks" if self.remat_blocks else "none"

    def __post_init__(self):
        from tpu_ddp.memory import validate_act_dtype, validate_remat
        validate_remat(self.remat)
        validate_act_dtype(self.act_dtype)
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0, 1), got "
                             f"{self.dropout_rate}")
        if self.kv_heads < 1:
            raise ValueError(f"num_kv_heads must be >= 1, got "
                             f"{self.kv_heads}")
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by "
                f"num_kv_heads={self.kv_heads}")

    @property
    def _tp(self) -> int:
        return self.tp_size if self.tp_axis is not None else 1

    @property
    def _ep(self) -> int:
        return self.ep_size if self.ep_axis is not None else 1

    # ---- parameters ----------------------------------------------------

    def init(self, key) -> dict:
        """GLOBAL parameter pytree (sharding is the trainer's job).

        Layouts are chosen so tensor-parallel sharding is a clean axis
        split (:meth:`param_specs`): ``wqkv`` is (dm, 3, heads, head_dim)
        and ``wo`` is (heads, head_dim, dm) — the head axis shards over
        ``tp``; ``w1``/``w2`` shard on the ``d_ff`` axis.
        """
        dm, dff, v = self.d_model, self.d_ff, self.vocab_size
        h, hd = self.num_heads, self.head_dim
        std = 0.02
        keys = iter(jax.random.split(key, 4 + 8 * self.num_layers))
        params = {
            "embed": _normal(next(keys), (v, dm), std, self.param_dtype),
            "ln_f": {"scale": jnp.ones((dm,), self.param_dtype),
                     "bias": jnp.zeros((dm,), self.param_dtype)},
            "head": _normal(next(keys), (dm, v), std, self.param_dtype),
        }
        blocks = []
        E = self.moe_experts
        for _ in range(self.num_layers):
            blk = {
                "ln1": {"scale": jnp.ones((dm,), self.param_dtype),
                        "bias": jnp.zeros((dm,), self.param_dtype)},
                "wo": _normal(next(keys), (h, hd, dm), std,
                              self.param_dtype),
                "ln2": {"scale": jnp.ones((dm,), self.param_dtype),
                        "bias": jnp.zeros((dm,), self.param_dtype)},
            }
            if self.is_gqa:
                # Separate Q and (smaller) KV projections; the fused
                # "wqkv" layout stays reserved for MHA back-compat.
                blk["wq"] = _normal(next(keys), (dm, h, hd), std,
                                    self.param_dtype)
                blk["wkv"] = _normal(next(keys), (dm, 2, self.kv_heads,
                                                  hd), std,
                                     self.param_dtype)
            else:
                blk["wqkv"] = _normal(next(keys), (dm, 3, h, hd), std,
                                      self.param_dtype)
            if E:
                # MoE MLP: stacked expert weights + a router.
                blk["router"] = _normal(next(keys), (dm, E), std,
                                        self.param_dtype)
                blk["w1"] = _normal(next(keys), (E, dm, dff), std,
                                    self.param_dtype)
                blk["w2"] = _normal(next(keys), (E, dff, dm), std,
                                    self.param_dtype)
            else:
                blk["w1"] = _normal(next(keys), (dm, dff), std,
                                    self.param_dtype)
                blk["w2"] = _normal(next(keys), (dff, dm), std,
                                    self.param_dtype)
            blocks.append(blk)
        params["blocks"] = tuple(blocks)
        return params

    def param_specs(self) -> dict:
        """Pytree of ``PartitionSpec``s mirroring :meth:`init`'s tree.

        The authoritative tensor-parallel layout: attention head axis and
        MLP hidden axis shard over ``tp_axis``; everything else (LayerNorm,
        embeddings, LM head) is replicated. With ``tp_size == 1`` every
        leaf is fully replicated.
        """
        tp = self.tp_axis if self._tp > 1 else None
        ep = self.ep_axis if self._ep > 1 else None
        ln = {"scale": P(), "bias": P()}
        blk = {
            "ln1": dict(ln),
            "wo": P(tp, None, None),
            "ln2": dict(ln),
        }
        if self.is_gqa:
            blk["wq"] = P(None, tp, None)
            blk["wkv"] = P(None, None, tp, None)
        else:
            blk["wqkv"] = P(None, None, tp, None)
        if self.moe_experts:
            blk["router"] = P()
            blk["w1"] = P(ep, None, tp)
            blk["w2"] = P(ep, tp, None)
        else:
            blk["w1"] = P(None, tp)
            blk["w2"] = P(tp, None)
        return {
            "embed": P(),
            "ln_f": dict(ln),
            "head": P(),
            "blocks": tuple(dict(blk) for _ in range(self.num_layers)),
        }

    # ---- forward -------------------------------------------------------

    def check_seq_len(self, local_len: int) -> None:
        """Validate the GLOBAL sequence length (local x sp under
        sequence parallelism) against ``max_seq_len``. The ONE home of
        this invariant — the dense trunk and the pipeline entry points
        (tpu_ddp/parallel/pipeline.py) both call it, so the sp-aware
        length accounting cannot drift between the two paths."""
        sp = self.sp_size if self.sp_axis is not None else 1
        if local_len * sp > self.max_seq_len:
            raise ValueError(
                f"global sequence length {local_len * sp} (local "
                f"{local_len} x sp {sp}) exceeds "
                f"max_seq_len={self.max_seq_len}")

    def _positions(self, lc: int):
        """Global positions of the local chunk (chunk offset under sp)."""
        if self.sp_axis is not None and self.sp_size > 1:
            start = lax.axis_index(self.sp_axis) * lc
        else:
            start = 0
        return start + jnp.arange(lc)

    def _tp_in(self, x):
        """Megatron ``f`` before a column-parallel matmul (no-op sans tp).

        Sits AFTER LayerNorm so the psum'd backward makes LN/embedding/
        residual gradients exact and replicated on every tp shard."""
        if self._tp > 1:
            return tp_input(x, self.tp_axis)
        return x

    def _tp_out(self, x):
        """Megatron ``g`` after a row-parallel matmul (no-op sans tp)."""
        if self._tp > 1:
            return tp_output(x, self.tp_axis)
        return x

    def _dropout(self, x, rng):
        """Inverted dropout; identity when inactive (rate 0 or no rng).
        The branch is static, so inactive configurations compile to the
        bare graph."""
        if rng is None or self.dropout_rate <= 0.0:
            return x
        keep = 1.0 - self.dropout_rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def apply(self, params, tokens, rng=None):
        """tokens: (B, L_local) int32 -> logits (B, L_local, V) float32.

        Under tensor parallelism ``params`` holds this shard's slices
        (heads and d_ff split ``tp_size``-ways, :meth:`param_specs`); the
        residual stream stays replicated, with one ``psum`` after each of
        the two row-parallel projections. ``rng`` activates dropout
        (training); omit it for deterministic eval.
        """
        return self.apply_with_aux(params, tokens, rng=rng)[0]

    def apply_with_aux(self, params, tokens, rng=None):
        """Like :meth:`apply`, additionally returning the mean Switch
        load-balance auxiliary loss over MoE blocks (0.0 when dense)."""
        x, aux = self.trunk_with_aux(params, tokens, rng=rng)
        return self.project(params, x), aux

    def project(self, params, x):
        """Vocabulary projection of post-LN activations — the ONE place
        the head matmul's precision is decided. Routed through
        :func:`tpu_ddp.ops.quant.qdot` so an int8-quantized serving
        tree (decode_quant, ops/quant.py) runs the fused weight-only
        matmul; a plain fp tree traces the identical dot."""
        from tpu_ddp.ops.quant import qdot
        with jax.named_scope("head"):
            logits = qdot(x, params["head"], self.compute_dtype)
            return logits.astype(jnp.float32)

    def trunk_with_aux(self, params, tokens, rng=None, stats=None):
        """Everything but the vocabulary projection: embed -> blocks ->
        final LayerNorm, returning ((B, L, dm) activations, aux). The
        split exists so the LM loss can fuse the head matmul into a
        chunked-vocab cross-entropy without materializing (T, V) logits
        (tpu_ddp/ops/loss.py chunked_vocab_cross_entropy). This is the
        single full-forward implementation — :meth:`apply` /
        :meth:`apply_with_aux` wrap it, so validation lives here once.

        ``rng``: dropout key (pre-decorrelated across data shards by the
        trainer); None disables dropout. ``stats``: optional mutable
        list collecting each MoE block's routing-health dict
        (tpu_ddp/parallel/moe.py routing_stats) — forces the direct
        block path (no remat), so pass it only on diagnostic runs."""
        cd = self.compute_dtype
        lc = tokens.shape[1]
        self.check_seq_len(lc)
        pos = self._positions(lc)
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(cd)
            if rng is not None:
                x = self._dropout(
                    x, jax.random.fold_in(rng, self.num_layers))
        aux = jnp.float32(0.0)
        from tpu_ddp.memory import cast_saved, effective_remat, wrap_stage
        remat = effective_remat(self.remat_policy, "attn")
        if stats is not None or (remat == "none"
                                 and self.act_dtype == "compute"):
            def blk_fn(blk, x, pos, r):
                return self.block_apply_aux(blk, x, pos, r, stats=stats)
        else:
            # _block_entry re-enters compute_dtype, so the boundary
            # cast below only changes what autodiff SAVES.
            blk_fn = wrap_stage(self._block_entry, remat)
        for i, blk in enumerate(params["blocks"]):
            r = jax.random.fold_in(rng, i) if rng is not None else None
            x, a = blk_fn(blk, cast_saved(x, self.act_dtype, cd), pos, r)
            aux = aux + a
        with jax.named_scope("head"):
            x = layer_norm(x, params["ln_f"]["scale"],
                           params["ln_f"]["bias"])
        return x, aux / max(self.num_layers, 1)

    def block_apply(self, blk, x, pos):
        """One transformer block: (B, L, dm) -> (B, L, dm).

        Factored out so the pipeline engine can ``lax.scan`` it over a
        stage's stacked layer slice (tpu_ddp/parallel/pipeline.py) while
        the dense path loops over the blocks tuple. For MoE blocks the
        router's auxiliary loss is discarded here; use
        :meth:`block_apply_aux` / :meth:`apply_with_aux` to train with
        the load-balance regularizer.
        """
        return self.block_apply_aux(blk, x, pos)[0]

    def qkv_proj(self, blk, y, pos):
        """Projected + RoPE'd q (B, L, H/tp, hd) and k/v (B, L, KV/tp,
        hd) from normalized input ``y`` (``_tp_in`` already applied by
        the caller under tensor parallelism). Column-parallel: local
        heads only, zero communication. One fused "wqkv" matmul for MHA;
        separate "wq"/"wkv" for GQA (KV/tp heads, the smaller
        projection). Shared by training (block_apply_aux) and KV-cache
        decode (models/generate.py). The projections route through
        :func:`tpu_ddp.ops.quant.qdot` (identical trace for fp trees;
        fused int8 matmul for a quantized serving tree)."""
        from tpu_ddp.ops.quant import qdot
        cd = self.compute_dtype
        b, lc, hd = y.shape[0], y.shape[1], self.head_dim
        h_loc = self.num_heads // self._tp
        # Dispatch on the STATIC config, not the params keys: a config/
        # checkpoint layout mismatch then fails immediately with a
        # KeyError instead of silently training the other scheme.
        if not self.is_gqa:
            qkv = qdot(y, blk["wqkv"], cd, reshape=(self.d_model, -1))
            qkv = qkv.astype(cd).reshape(b, lc, 3, h_loc, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            kv_loc = self.kv_heads // self._tp
            q = qdot(y, blk["wq"], cd, reshape=(self.d_model, -1))
            q = q.astype(cd).reshape(b, lc, h_loc, hd)
            kvp = qdot(y, blk["wkv"], cd, reshape=(self.d_model, -1))
            kvp = kvp.astype(cd).reshape(b, lc, 2, kv_loc, hd)
            k, v = kvp[:, :, 0], kvp[:, :, 1]
        return rope(q, pos), rope(k, pos), v

    def _block_entry(self, blk, x, pos, rng=None):
        """:meth:`block_apply_aux` with the residual stream re-entering
        ``compute_dtype`` — the checkpoint-region entry point under a
        memory policy (the saved boundary input is in ``act_dtype``,
        the block arithmetic is not)."""
        return self.block_apply_aux(blk, x.astype(self.compute_dtype),
                                    pos, rng)

    def block_apply_aux(self, blk, x, pos, rng=None, stats=None):
        cd = self.compute_dtype
        b, lc = x.shape[0], x.shape[1]
        h_loc, hd = self.num_heads // self._tp, self.head_dim
        r1 = r2 = None
        if rng is not None:
            # Branch keys derive from this block's key; the trainer
            # already decorrelated ``rng`` across data shards (and left
            # it IDENTICAL across mp shards — the residual stream is
            # replicated over tp, so its mask must be too).
            r1, r2 = jax.random.split(rng)
        with jax.named_scope("attn"):
            y = layer_norm(x, blk["ln1"]["scale"], blk["ln1"]["bias"])
            # Under GQA k/v stay at KV-head width end to end: every
            # attend() path contracts grouped — ring/blockwise/full in
            # jnp, and the flash kernel indexes K/V blocks by q-head
            # group natively — so collectives, memory and score math all
            # carry KV-width bytes.
            q, k, v = self.qkv_proj(blk, self._tp_in(y), pos)
            o = attend(q, k, v, causal=True, axis_name=self.sp_axis,
                       axis_size=self.sp_size, flash=self.use_flash,
                       mode=self.sp_mode)
            # Row-parallel output projection: partial sums psum'd over tp.
            wo = blk["wo"].astype(cd).reshape(h_loc * hd, self.d_model)
            o = self._tp_out(jnp.dot(
                o.reshape(b, lc, h_loc * hd), wo,
                preferred_element_type=jnp.float32)).astype(cd)
            x = x + self._dropout(o, r1)
        with jax.named_scope("mlp"):
            y = layer_norm(x, blk["ln2"]["scale"], blk["ln2"]["bias"])
            if self.moe_experts:
                from tpu_ddp.parallel.moe import moe_mlp
                y, aux = moe_mlp(
                    y, blk["router"], blk["w1"], blk["w2"],
                    num_experts=self.moe_experts,
                    capacity_factor=self.moe_capacity_factor,
                    top_k=self.moe_top_k,
                    ep_axis=self.ep_axis or "ep", ep_size=self._ep,
                    tp_in=self._tp_in, tp_out=self._tp_out, stats=stats)
                return x + self._dropout(y, r2), aux
            # Column-parallel up-projection (local d_ff slice) ...
            y = jnp.dot(self._tp_in(y), blk["w1"].astype(cd),
                        preferred_element_type=jnp.float32)
            y = jax.nn.gelu(y.astype(jnp.float32)).astype(cd)
            # ... row-parallel down-projection, psum'd.
            y = self._tp_out(jnp.dot(
                y, blk["w2"].astype(cd),
                preferred_element_type=jnp.float32)).astype(cd)
            return x + self._dropout(y, r2), jnp.float32(0.0)

    def route_stats(self, params, tokens):
        """Diagnostic routing-health probe: one deterministic trunk
        pass (no dropout) collecting each MoE block's routing counters
        — list of dicts with ``dropped_frac``, ``expert_load`` (E,),
        and ``imbalance`` (tpu_ddp/parallel/moe.py routing_stats), one
        per layer, [] for a dense model. Routing is per-token and
        partition-independent, so callers holding sharded training
        params strip the partition axes and run this on the canonical
        tree (tpu_ddp/train/lm.py LMTrainer.route_stats does exactly
        that)."""
        if not self.moe_experts:
            return []
        stats: list = []
        self.trunk_with_aux(params, tokens, rng=None, stats=stats)
        return stats

    def head_apply(self, params, x):
        """Final LayerNorm + LM head: (B, L, dm) -> (B, L, V) float32."""
        with jax.named_scope("head"):
            x = layer_norm(x, params["ln_f"]["scale"],
                           params["ln_f"]["bias"])
        return self.project(params, x)

    def num_params(self, params=None, key=None) -> int:
        if params is None:
            params = self.init(key if key is not None else jax.random.key(0))
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def with_sequence_parallel(self, axis_name: str, axis_size: int,
                               mode: str = "ring") -> "TransformerLM":
        if mode not in ("ring", "ulysses"):
            raise ValueError(f"unknown sequence-parallel mode {mode!r}; "
                             "expected 'ring' or 'ulysses'")
        if mode == "ulysses" and (self.num_heads // self._tp) % axis_size:
            raise ValueError(
                f"ulysses needs (num_heads/tp) % sp == 0 (got heads="
                f"{self.num_heads}/{self._tp} per tp shard, sp={axis_size})")
        return dataclasses.replace(self, sp_axis=axis_name,
                                   sp_size=axis_size, sp_mode=mode)

    def with_tensor_parallel(self, axis_name: str,
                             axis_size: int) -> "TransformerLM":
        if self.num_heads % axis_size:
            raise ValueError(f"num_heads={self.num_heads} not divisible by "
                             f"tp={axis_size}")
        if self.kv_heads % axis_size:
            raise ValueError(f"num_kv_heads={self.kv_heads} not divisible "
                             f"by tp={axis_size}")
        if self.d_ff % axis_size:
            raise ValueError(f"d_ff={self.d_ff} not divisible by "
                             f"tp={axis_size}")
        # Re-validate an already-configured Ulysses sp against the PER-TP
        # head count (trainers apply sp before tp, so the sp-time check
        # ran with tp=1) — fail at construction, not inside the jit trace.
        if (self.sp_mode == "ulysses" and self.sp_size > 1
                and (self.num_heads // axis_size) % self.sp_size):
            raise ValueError(
                f"ulysses needs (num_heads/tp) % sp == 0 (got heads="
                f"{self.num_heads}/{axis_size} per tp shard, "
                f"sp={self.sp_size})")
        return dataclasses.replace(self, tp_axis=axis_name,
                                   tp_size=axis_size)

    def with_expert_parallel(self, axis_name: str,
                             axis_size: int) -> "TransformerLM":
        if not self.moe_experts:
            raise ValueError("expert parallelism requires a MoE model "
                             "(moe_experts > 0)")
        if self.moe_experts % axis_size:
            raise ValueError(f"moe_experts={self.moe_experts} not "
                             f"divisible by ep={axis_size}")
        return dataclasses.replace(self, ep_axis=axis_name,
                                   ep_size=axis_size)


def make_transformer(name: str = "TransformerLM-small",
                     **kwargs) -> TransformerLM:
    presets = {
        "TransformerLM-tiny": dict(num_layers=2, num_heads=4, d_model=128,
                                   d_ff=512, vocab_size=1024),
        "TransformerLM-small": dict(num_layers=4, num_heads=8, d_model=512,
                                    d_ff=2048, vocab_size=32000),
        "TransformerLM-base": dict(num_layers=12, num_heads=12, d_model=768,
                                   d_ff=3072, vocab_size=32000),
        # MXU-saturating single-chip bench config (~740M params): every
        # matmul has K,N >= 2048 and head_dim 128 fills the MXU tile
        # exactly; fits a 16 GB v5e with f32 AdamW states + remat.
        "TransformerLM-large": dict(num_layers=12, num_heads=16,
                                    d_model=2048, d_ff=8192,
                                    vocab_size=32000, remat="blocks"),
        # Long-context zoo entries (DESIGN.md §27): tiny compute dims
        # so CPU tests and the long-context sweep trace fast, with a
        # max_seq_len far past what one hot KV tier holds — prompt
        # length, not model size, is what these exist to stress.
        "TransformerLM-tiny-8k": dict(num_layers=2, num_heads=4,
                                      d_model=128, d_ff=512,
                                      vocab_size=1024,
                                      max_seq_len=8192),
        "TransformerLM-small-32k": dict(num_layers=4, num_heads=8,
                                        d_model=512, d_ff=2048,
                                        vocab_size=32000,
                                        max_seq_len=32768),
        # MoE zoo family (DESIGN.md §28): Switch (top-1) at the small
        # end, GShard (top-2) at scale. d_ff is the PER-EXPERT hidden
        # width, so param count grows ~linearly in moe_experts while
        # per-token FLOPs track top_k — the capability-per-FLOP trade
        # the family exists to buy (experiments/moe_sweep.json).
        "TransformerLM-moe-tiny": dict(num_layers=2, num_heads=4,
                                       d_model=128, d_ff=256,
                                       vocab_size=1024, moe_experts=4,
                                       moe_top_k=1,
                                       moe_capacity_factor=1.25),
        "TransformerLM-moe-small": dict(num_layers=4, num_heads=8,
                                        d_model=512, d_ff=1024,
                                        vocab_size=32000, moe_experts=8,
                                        moe_top_k=2,
                                        moe_capacity_factor=1.25),
        # LM-large's sparse sibling: same trunk geometry, 16 experts of
        # half the dense d_ff — ~4.3x the dense family's MLP params at
        # top-2 per-token compute close to dense (cap algebra in
        # DESIGN.md §28); remat="blocks" like its dense twin.
        "TransformerLM-moe-large": dict(num_layers=12, num_heads=16,
                                        d_model=2048, d_ff=4096,
                                        vocab_size=32000,
                                        moe_experts=16, moe_top_k=2,
                                        moe_capacity_factor=1.25,
                                        remat="blocks"),
    }
    if name not in presets:
        raise ValueError(f"unknown transformer preset {name!r}; "
                         f"available: {sorted(presets)}")
    cfg = dict(presets[name])
    cfg.update(kwargs)
    return TransformerLM(name=name, **cfg)
