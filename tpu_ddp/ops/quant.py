"""Weight-only int8 quantization for decode compute (DESIGN.md §26).

Decode is memory-bandwidth-bound: every engine step streams the full
parameter set from HBM to produce one (or, speculatively, k+1) tokens
per sequence. Weight-only quantization attacks exactly that wall —
the dense/attention projection weights are stored per-output-channel
int8 (4x fewer bytes than f32) and dequantized INSIDE the matmul:

    y @ W  ≈  (y @ Q) * s        Q int8 (in, out), s f32 (out,)

The scale factors commute with the contraction because they are
per-OUTPUT-column — the fp weights are never materialized, so the
compute path reads int8 bytes. Activations, embeddings and LayerNorms
stay in the compute dtype: the quality cliff of activation
quantization is not worth the bytes (embed is a gather, not a matmul).

Two execution paths, one contract:

- :func:`qdot` — the ONE dispatch point every decode-path matmul
  routes through (models/transformer.py ``qkv_proj``/``project``,
  models/decode.py ``mlp``/``block_finish``). For a plain array it
  traces byte-for-byte the pre-quantization program (same astype/
  reshape/dot sequence), so fp engines are bitwise unchanged. For a
  :class:`QuantizedWeight` it runs the fused int8 matmul.
- On TPU the fused matmul is the Pallas kernel
  (ops/pallas/quant_matmul.py): int8 tiles stream into VMEM, convert
  on the MXU's doorstep, and the per-column scale fuses into the
  epilogue. Off-TPU the reference XLA path computes the identical
  ``dot(x, q.astype(f32)) * s`` contraction.

:class:`QuantizedWeight` is a registered pytree node, so a quantized
parameter tree flows through ``jax.jit`` argument passing, donation
and ``tree.map`` exactly like a dense one — the serving engine keys
its memoized program caches on the treedef, which differs from the fp
tree's, giving quantized programs their own jit cache entries for
free. The quality bar is the compress-sweep convention: mean NLL of a
seeded eval stream within 0.25% of the fp32 model
(:func:`nll_drift`, enforced by tests/test_speculative.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["QuantizedWeight", "quantize_weight", "dequantize",
           "quantize_params", "qdot", "decode_forward_logits",
           "stream_nll", "nll_drift", "DECODE_QUANTS"]

DECODE_QUANTS = ("none", "int8")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantizedWeight:
    """One int8-quantized weight in matmul layout: ``q`` (in, out)
    int8, ``s`` (out,) f32 per-output-channel scales. Symmetric
    (no zero point): ``W ≈ q * s``."""

    q: jax.Array
    s: jax.Array

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes_dense_f32(self) -> int:
        return 4 * int(self.q.size)


def quantize_weight(w, reshape=None) -> QuantizedWeight:
    """Per-output-channel symmetric int8: ``s_c = max|w[:, c]| / 127``,
    ``q = round(w / s)``. ``reshape`` first brings a multi-axis weight
    into its 2-D (in, out) matmul layout (the same reshape the fp
    matmul call site applies), so quantization channels are exactly
    the matmul's output columns."""
    w = jnp.asarray(w, jnp.float32)
    if reshape is not None:
        w = w.reshape(reshape)
    if w.ndim != 2:
        raise ValueError(f"quantize_weight wants a 2-D matmul layout, "
                         f"got shape {w.shape}")
    amax = jnp.max(jnp.abs(w), axis=0)
    # An all-zero column quantizes to zeros under any scale; 1.0 keeps
    # the division finite without changing the result.
    s = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return QuantizedWeight(q=q, s=s.astype(jnp.float32))


def dequantize(qw: QuantizedWeight):
    """fp32 reconstruction ``q * s`` — tests and error bounds only;
    the serving path never materializes this."""
    return qw.q.astype(jnp.float32) * qw.s[None, :]


def qdot(y, w, cd, reshape=None):
    """The one decode-path matmul dispatch: ``y @ w`` in f32 accum.

    Plain array ``w``: exactly the pre-quantization program —
    ``dot(y, w.astype(cd).reshape(reshape))`` with f32 accumulation,
    bitwise identical to the inlined call sites it replaced.
    :class:`QuantizedWeight`: the fused weight-only int8 matmul
    (``reshape`` is ignored — quantized weights are stored in matmul
    layout). Returns f32 (callers cast back to ``cd`` exactly where
    the fp code did)."""
    if isinstance(w, QuantizedWeight):
        if jax.default_backend() == "tpu":
            from tpu_ddp.ops.pallas.quant_matmul import int8_matmul
            return int8_matmul(y.astype(cd), w.q, w.s)
        # Reference XLA path: the scale is per-output-column, so it
        # commutes with the contraction — dequant AFTER the dot keeps
        # the weight reads int8.
        acc = jnp.dot(y.astype(cd), w.q.astype(cd),
                      preferred_element_type=jnp.float32)
        return acc * w.s
    w = w.astype(cd)
    if reshape is not None:
        w = w.reshape(reshape)
    return jnp.dot(y, w, preferred_element_type=jnp.float32)


def quantize_params(model, params):
    """Quantize every decode-path projection of a dense transformer
    parameter tree: per-block wqkv/wq/wkv, wo, w1/w2, plus the LM
    head. Embedding and LayerNorm leaves pass through untouched (they
    are gathers/normalizations, not matmuls). Returns a NEW tree with
    the same dict structure; matmul leaves become
    :class:`QuantizedWeight` in their 2-D matmul layout (the reshape
    their fp call sites applied)."""
    dm = model.d_model

    def one_block(blk):
        out = dict(blk)
        for name in ("wqkv", "wq", "wkv"):
            if name in blk:
                out[name] = quantize_weight(blk[name], reshape=(dm, -1))
        out["wo"] = quantize_weight(blk["wo"], reshape=(-1, dm))
        out["w1"] = quantize_weight(blk["w1"])
        out["w2"] = quantize_weight(blk["w2"])
        return out

    out = dict(params)
    out["blocks"] = tuple(one_block(blk) for blk in params["blocks"])
    out["head"] = quantize_weight(params["head"])
    return out


def decode_forward_logits(model, params, tokens):
    """Full-sequence logits (B, L, V) through the DECODE math path
    (project_qkv / attend_cached / block_finish / head_apply) — the
    path :func:`qdot` routes, so it accepts fp and quantized trees
    alike. This is the quality-bar forward: it evaluates exactly the
    program the serving engine runs, not the training ``apply``."""
    from tpu_ddp.models.decode import (attend_cached, block_finish,
                                       project_qkv)

    cd = model.compute_dtype
    b, L = tokens.shape
    pos = jnp.arange(L)
    x = params["embed"][tokens].astype(cd)
    for blk in params["blocks"]:
        q, k, v = project_qkv(model, blk, x, pos)
        o = attend_cached(model, q, k.astype(cd), v.astype(cd), pos)
        x = block_finish(model, blk, x, o)
    return model.head_apply(params, x)


def stream_nll(model, params, tokens) -> jax.Array:
    """Mean next-token NLL of ``tokens`` (B, L) under ``params``
    through the decode path — the scalar the 0.25%-of-fp32 quality
    bar compares."""
    logits = decode_forward_logits(model, params, tokens)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)
    return jnp.mean(nll)


def nll_drift(model, params, qparams, tokens) -> dict:
    """The committed quality metric for ``decode_quant``: relative
    mean-NLL drift of the quantized tree vs the fp tree on a seeded
    eval stream, plus greedy next-token agreement (reported, not
    gated). The bar (≤ 0.25%, the compress-sweep convergence-drift
    convention) is enforced by the callers."""
    lf = decode_forward_logits(model, params, tokens)
    lq = decode_forward_logits(model, qparams, tokens)
    nll_f = float(stream_nll(model, params, tokens))
    nll_q = float(stream_nll(model, qparams, tokens))
    agree = float(jnp.mean(jnp.argmax(lf, -1) == jnp.argmax(lq, -1)))
    return {
        "nll_fp32": nll_f,
        "nll_int8": nll_q,
        "rel_drift": abs(nll_q - nll_f) / max(abs(nll_f), 1e-12),
        "greedy_agreement": agree,
        "max_abs_logit_err": float(jnp.max(jnp.abs(lq - lf))),
    }
