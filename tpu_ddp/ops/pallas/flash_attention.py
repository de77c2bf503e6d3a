"""Flash attention — Pallas TPU kernel, forward and backward.

The LM family's hot op (tpu_ddp/models/transformer.py attention). The
jnp path (tpu_ddp/parallel/ring_attention.py:full_attention) materializes
the (L, L) score matrix in HBM; this kernel streams K/V blocks through
VMEM with the online-softmax recurrence (Dao et al., "FlashAttention",
arXiv:2205.14135 — reimplemented from the paper's algorithm, not from any
code), so HBM traffic is O(L·D) and peak memory per core is one
(block_q, block_k) tile. The backward pass recomputes probabilities from
the saved logsumexp in two sweeps (dk/dv with k-blocks resident, then dq
with q-blocks resident) — the standard flash backward.

TPU mapping:
- grid = (batch·heads, q-blocks, kv-blocks) with the kv axis innermost:
  TPU grid steps are sequential, so the online-softmax state (running
  max / sum / accumulator) lives in VMEM scratch that persists across
  the kv sweep, and outputs are written on the sweep's last step;
- blocks are 128x128 (MXU-shaped); sequence length is zero-padded to a
  multiple of 128 and head dim to 64 or a multiple of 128 (``_pad_d``),
  with validity masks from absolute positions so padding never
  contributes;
- all matmuls run on the MXU via ``preferred_element_type=float32``;
  the softmax state is float32 regardless of input dtype.

Runs compiled on TPU and in interpreter mode elsewhere (CI's virtual CPU
mesh). Exactness vs the jnp reference — values and gradients, causal and
not, padded and aligned shapes — is tested in tests/test_flash_attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK = 128
_NEG_INF = -1e30


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _pad_d(d: int) -> int:
    """Padded head dim. Head dims <= 64 stay at 64 — Mosaic handles a
    64-lane minor dim natively (same rule as jax's reference TPU flash
    kernel, which only requires a multiple of 128 when head_dim > 128),
    and every matmul touching d halves its FLOPs vs padding to 128.
    Round-2 verdict: the old blanket pad-to-128 doubled both attention
    matmuls for the presets' head_dim 64."""
    if d <= 64:
        return 64
    return _cdiv(d, _BLOCK) * _BLOCK


def _pick_block(lp: int, want: int) -> int:
    """Largest power-of-two block <= ``want`` dividing the padded length.
    Bigger tiles amortize the per-grid-step scratch read-modify-write and
    feed the MXU larger matmuls; lp is always a multiple of 128."""
    b = want
    while b > _BLOCK and lp % b:
        b //= 2
    return min(b, lp)


def _block_env(name: str, default: int) -> int:
    """Block-size tuning hook (TPU_DDP_FLASH_{BQ,BK,BWD_BQ,BWD_BK}):
    read at trace time, so a bench sweep can try tile shapes without a
    code edit. Trace-time means once a given shape has been traced in a
    process, jax's jit cache (keyed on avals, not env) silently reuses
    the previously-traced tiles — an in-process sweep would record
    identical timings for "different" tiles. Each tile configuration
    therefore needs a fresh process (the round-4 sweep ran one
    subprocess per tile config for exactly this reason).
    Defaults are the shipped, measured-best values (v5e
    sweep, round 4): fwd 512/1024 and bwd 512/512 beat the previous
    256/512 + 256/256 by 14% on the TransformerLM-large step (0.512 ->
    0.586 MFU at batch 4 seq 2048), +28% on the small LM, +46% at seq
    8192 — bigger tiles amortize the per-grid-step scratch
    read-modify-write and feed the MXU larger matmuls.

    Must be a power of two >= the 128 lane width: _pick_block halves the
    want until it divides the padded length, which only terminates on a
    divisor for powers of two (lp is always a multiple of 128) — a
    non-power-of-two value would leave tail rows silently unprocessed
    (the kernel grids floor-divide), so it is refused loudly here."""
    import os
    v = int(os.environ.get(name, default))
    if v < _BLOCK or (v & (v - 1)):
        raise ValueError(f"{name}={v}: must be a power of two "
                         f">= {_BLOCK}")
    return v


def _positions(i, j, bq, bk):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos, k_pos


def _masked_scores(q, k, i, j, *, scale, seq_len, causal):
    """(bq, bk) f32 scores with padding + causal masking applied.

    Inputs stay in their storage dtype (bf16 rides the MXU's fast path);
    accumulation is f32 via preferred_element_type."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    q_pos, k_pos = _positions(i, j, q.shape[0], k.shape[0])
    ok = (q_pos < seq_len) & (k_pos < seq_len)
    if causal:
        ok &= k_pos <= q_pos
    return jnp.where(ok, s, _NEG_INF)


def _block_visible(i_q, j_k, bq, bk):
    """False iff the (q-block, k-block) pair is entirely above the causal
    diagonal (no q_pos >= k_pos) — its compute can be skipped outright."""
    return j_k * bk <= (i_q + 1) * bq - 1


# ---- forward ------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, scale, seq_len, causal):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    bq, bk = q_ref.shape[1], k_ref.shape[1]

    def update():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _masked_scores(q, k, i, j, scale=scale, seq_len=seq_len,
                           causal=causal)
        m_prev = m_sc[:, :1]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[:] = acc_sc[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    if causal:
        # Skip blocks entirely above the diagonal — ~2x less compute.
        pl.when(_block_visible(i, j, bq, bk))(update)
    else:
        update()

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l_safe = jnp.maximum(l_sc[:, :1], 1e-30)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        # lse block is the FULL (1, 1, Lp) row (TPU block tiling forbids
        # a (1, bq) sub-row block); each q-block writes its slice.
        bq = q_ref.shape[1]
        lse_ref[0, :, pl.ds(i * bq, bq)] = \
            (m_sc[:, :1] + jnp.log(l_safe)).T


def _kv_index(b, *, n_heads, n_kv):
    """Grid dim-0 runs over B*H q-heads; the K/V array holds B*KV heads.
    Group-contiguous mapping (head h shares KV head h // (H/KV) — the
    ``jnp.repeat`` order): kv_row = (b // H) * KV + (b % H) // (H/KV).
    Identity when H == KV (MHA)."""
    if n_heads == n_kv:
        return b
    group = n_heads // n_kv
    return (b // n_heads) * n_kv + (b % n_heads) // group


@functools.partial(jax.jit,
                   static_argnames=("scale", "seq_len", "causal",
                                    "n_heads", "n_kv", "interpret"))
def _fwd_impl(q3, k3, v3, *, scale, seq_len, causal, n_heads, n_kv,
              interpret):
    bh, lp, dp = q3.shape
    bq = _pick_block(lp, _block_env("TPU_DDP_FLASH_BQ", 512))
    bk = _pick_block(lp, _block_env("TPU_DDP_FLASH_BK", 1024))
    kv_idx = functools.partial(_kv_index, n_heads=n_heads, n_kv=n_kv)
    qkv_spec = lambda which, blk: pl.BlockSpec(  # noqa: E731
        (1, blk, dp),
        {"q": lambda b, i, j: (b, i, 0),
         "kv": lambda b, i, j: (kv_idx(b), j, 0)}[which],
        memory_space=pltpu.VMEM)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, seq_len=seq_len,
                          causal=causal),
        grid=(bh, lp // bq, lp // bk),
        in_specs=[qkv_spec("q", bq), qkv_spec("kv", bk),
                  qkv_spec("kv", bk)],
        out_specs=(qkv_spec("q", bq),
                   pl.BlockSpec((1, 1, lp), lambda b, i, j: (b, 0, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct((bh, 1, lp), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, dp), jnp.float32)],
        name="flash_fwd",
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse


# ---- backward -----------------------------------------------------------

def _recompute_p_ds(q, k, v, do, lse_row, delta_row, i, j, *, scale,
                    seq_len, causal):
    """Shared backward algebra: p = exp(s - lse), ds = p*(dp - delta).

    ``lse_row``/``delta_row`` are (1, bq) blocks; transposed to column
    vectors here (2-D throughout for TPU layouts)."""
    s = _masked_scores(q, k, i, j, scale=scale, seq_len=seq_len,
                       causal=causal)
    p = jnp.exp(s - lse_row.T)                             # (bq, bk)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = (p * (dp - delta_row.T) * scale).astype(q.dtype)
    return p.astype(q.dtype), ds


def _bwd_kv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_sc, dv_sc, *, scale, seq_len,
                   causal, n_q_blocks):
    """dk/dv sweep. Grid dim 0 runs over B*KV (the K/V rows); the inner
    dim enumerates (group member g, q-block iq) pairs as c = g *
    n_q_blocks + iq, so under grouped-query attention every q-head
    sharing this KV head accumulates into the SAME scratch before one
    flush (TPU grid steps are sequential). MHA is group == 1, where c is
    simply iq."""
    jk, c = pl.program_id(1), pl.program_id(2)
    iq = c % n_q_blocks

    @pl.when(c == 0)
    def _():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    bq, bk = q_ref.shape[1], k_ref.shape[1]

    def update():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _recompute_p_ds(q, k, v, do,
                                lse_ref[0, :, pl.ds(iq * bq, bq)],
                                delta_ref[0, :, pl.ds(iq * bq, bq)],
                                iq, jk, scale=scale, seq_len=seq_len,
                                causal=causal)
        dv_sc[:] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dk_sc[:] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    if causal:
        pl.when(_block_visible(iq, jk, bq, bk))(update)
    else:
        update()

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_sc, *, scale, seq_len, causal):
    iq, jk = pl.program_id(1), pl.program_id(2)  # q-block outer, k inner

    @pl.when(jk == 0)
    def _():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    bq, bk = q_ref.shape[1], k_ref.shape[1]

    def update():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _recompute_p_ds(q, k, v, do,
                                lse_ref[0, :, pl.ds(iq * bq, bq)],
                                delta_ref[0, :, pl.ds(iq * bq, bq)],
                                iq, jk, scale=scale, seq_len=seq_len,
                                causal=causal)
        dq_sc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(_block_visible(iq, jk, bq, bk))(update)
    else:
        update()

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "seq_len", "causal",
                                    "n_heads", "n_kv", "interpret"))
def _bwd_impl(q3, k3, v3, o3, lse, do3, *, scale, seq_len, causal,
              n_heads, n_kv, interpret):
    bh, lp, dp = q3.shape
    bq = _pick_block(lp, _block_env("TPU_DDP_FLASH_BWD_BQ", 512))
    bk = _pick_block(lp, _block_env("TPU_DDP_FLASH_BWD_BK", 512))
    group = n_heads // n_kv
    nq = lp // bq
    kv_idx = functools.partial(_kv_index, n_heads=n_heads, n_kv=n_kv)
    # delta_i = rowsum(dO_i * O_i): one fused elementwise pass, f32.
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]                   # (bh, 1, lp)

    # ---- dk/dv sweep: grid dim 0 over the B*KV K/V rows; the inner dim
    # enumerates (group member, q-block) as c = g*nq + iq, so grouped
    # q-heads accumulate into one scratch (see _bwd_kv_kernel). For MHA
    # q_row(b, c) == b and the maps reduce to the plain layout.
    def q_row(b, c):
        if group == 1:
            return b
        return (b // n_kv) * n_heads + (b % n_kv) * group + c // nq

    def qspec_kv(blk):
        return pl.BlockSpec((1, blk, dp),
                            lambda b, a, c: (q_row(b, c), c % nq, 0),
                            memory_space=pltpu.VMEM)

    kvspec_kv = pl.BlockSpec((1, bk, dp), lambda b, a, c: (b, a, 0),
                             memory_space=pltpu.VMEM)
    # lse/delta ride as full (1, 1, Lp) rows; kernels slice their q-block
    # (TPU block tiling forbids a (1, bq) sub-row block).
    row_kv = pl.BlockSpec((1, 1, lp), lambda b, a, c: (q_row(b, c), 0, 0),
                          memory_space=pltpu.VMEM)

    kw = dict(scale=scale, seq_len=seq_len, causal=causal)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, n_q_blocks=nq, **kw),
        grid=(k3.shape[0], lp // bk, group * nq),
        in_specs=[qspec_kv(bq), kvspec_kv, kvspec_kv, qspec_kv(bq),
                  row_kv, row_kv],
        out_specs=(kvspec_kv, kvspec_kv),
        # Cotangent dtypes must match the primals' (k and v may differ).
        out_shape=(jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, dp), jnp.float32)] * 2,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)

    # ---- dq sweep: per q-head grid; K/V blocks via the grouped map.
    def block3(which, blk):
        return pl.BlockSpec(
            (1, blk, dp),
            {"outer": lambda b, a, c: (b, a, 0),
             "inner": lambda b, a, c: (kv_idx(b), c, 0)}[which],
            memory_space=pltpu.VMEM)

    row_spec = pl.BlockSpec((1, 1, lp), lambda b, a, c: (b, 0, 0),
                            memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_bwd_q_kernel, **kw),
        grid=(bh, lp // bq, lp // bk),  # q-blocks outer, k-blocks inner
        in_specs=[block3("outer", bq), block3("inner", bk),
                  block3("inner", bk), block3("outer", bq),
                  row_spec, row_spec],
        out_specs=block3("outer", bq),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dp), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# ---- public op ----------------------------------------------------------

def _interpret() -> bool:
    from tpu_ddp.ops.pallas import interpret_mode
    return interpret_mode()


def _to3(x, lp, dp):
    """(B, L, H, D) -> (B*H, Lp, Dp), zero-padded."""
    b, L, h, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, L, d)
    return jnp.pad(x, ((0, 0), (0, lp - L), (0, dp - d)))


def _from3(x3, b, L, h, d):
    return jnp.transpose(
        x3[:, :L, :d].reshape(b, h, L, d), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal: bool = False):
    """Exact multi-head attention, flash-style. (B, L, H, D) in and out.

    Drop-in replacement for
    tpu_ddp/parallel/ring_attention.py:full_attention — same math, O(L·D)
    HBM traffic instead of an O(L²) score matrix. Differentiable via the
    flash backward recomputation.

    Grouped-query attention: ``k``/``v`` may carry KV < H heads (H % KV
    == 0, group-contiguous ``jnp.repeat`` order). The kernels index K/V
    blocks by q-head group directly — the expansion is never
    materialized, and the backward accumulates each KV head's dk/dv
    across its group inside one scratch sweep.
    """
    o, _ = _flash_fwd_padded(q, k, v, causal)
    return o


def _check_heads(h: int, kvh: int) -> None:
    if h % kvh:
        raise ValueError(f"flash_attention: {h} query heads not "
                         f"divisible by {kvh} KV heads")


def _flash_fwd_padded(q, k, v, causal):
    b, L, h, d = q.shape
    kvh = k.shape[2]
    _check_heads(h, kvh)
    lp = _cdiv(L, _BLOCK) * _BLOCK
    dp = _pad_d(d)
    scale = 1.0 / (d ** 0.5)
    o3, lse = _fwd_impl(_to3(q, lp, dp), _to3(k, lp, dp), _to3(v, lp, dp),
                        scale=scale, seq_len=L, causal=causal,
                        n_heads=h, n_kv=kvh, interpret=_interpret())
    return _from3(o3, b, L, h, d), (o3, lse)


def _flash_fwd(q, k, v, causal):
    o, (o3, lse) = _flash_fwd_padded(q, k, v, causal)
    return o, (q, k, v, o3, lse)


def _flash_bwd(causal, residuals, g):
    q, k, v, o3, lse = residuals
    b, L, h, d = q.shape
    kvh = k.shape[2]
    lp = _cdiv(L, _BLOCK) * _BLOCK
    dp = _pad_d(d)
    scale = 1.0 / (d ** 0.5)
    dq3, dk3, dv3 = _bwd_impl(
        _to3(q, lp, dp), _to3(k, lp, dp), _to3(v, lp, dp), o3, lse,
        _to3(g, lp, dp), scale=scale, seq_len=L, causal=causal,
        n_heads=h, n_kv=kvh, interpret=_interpret())
    return (_from3(dq3, b, L, h, d), _from3(dk3, b, L, kvh, d),
            _from3(dv3, b, L, kvh, d))


flash_attention.defvjp(_flash_fwd, _flash_bwd)
