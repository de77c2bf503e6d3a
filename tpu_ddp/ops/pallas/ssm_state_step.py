"""One-token Mamba-2 state step — Pallas TPU kernel over the serving
state pool.

The decode step of a state-space layer (tpu_ddp/serve/engine.py
``state_step``) advances every decoding slot's recurrent state ``S``
(heads, head_dim, N) by one token and reads the token's output out of it
(tpu_ddp/models/hybrid.py ``advance_state``, the definition this kernel
is tested against):

    new = decay[s,h] * old + (dt*x)[s,h,p,None] * B[s,g,None,:]
    y[s,h,p] = sum_n new * C[s,g,:]

The state is the whole of the work: 268 MB a layer at the benchmark's
geometry (64 slots x 128 heads x 64 x 128 float32), and any
implementation reads it once and writes it once. As two XLA operations
(an in-place dynamic-update-slice with a select, then a reduction over
the minor dimension) it is moved three times: the new state is written
and read back for ``y``. Here a tile of the pool is brought on chip
once, advanced, written back, and ``y`` is summed out of the tile while
it is held.

TPU mapping:
- the WHOLE pool ``(state_layers, slots, heads, head_dim, N)`` is the
  operand, aliased input to output; the layer is a scalar-prefetch
  operand that the block index maps read, so the nine calls of a step
  are ONE kernel and nothing but layer ``si``'s tiles is touched;
- grid ``(slots, tiles a slot)``: a tile is up to ``_TILE_BYTES`` of one
  slot's heads (64 heads, 2 MB, at the benchmark's geometry), (head_dim,
  N) per head with N on the lanes. Tiles stream through the pipeline's
  double buffers, in and out; the kernel runs at the rate of a plain
  copy through them (0.83 ms a layer, PERF.md PR 34);
- ``B`` and ``C`` come at their group width (slots, groups, N): a row is
  broadcast along sublanes for free;
- ``dt*x`` is per (head, head_dim) and has to lie along SUBLANES beside
  the tile and be the same on every lane. The wrapper hands it over
  transposed, (head_dim, heads of the tile); spreading a head's column
  over the lanes is a cross-lane move for every register of state, and
  so is the sum over N, and both together are more than the cross-lane
  unit does in the time the tile streams (0.91 ms a layer). So the
  broadcast goes through the idle MXU instead: the tile of ``dt*x`` with
  every column but the head's zeroed, times a matrix of ones. Each
  output is one value times 1.0 plus zeros, and the three bfloat16
  parts that ``Precision.HIGHEST`` splits a float32 into add up to it
  again exactly: on the v5e the state came out bit for bit the
  lane-broadcast's (PERF.md PR 34);
- ``y`` leaves the same way, (head_dim, heads of the tile): each head's
  sums are put in their column of a value the loop over heads carries,
  stored once a tile; the two small transposes (2 MB) are XLA's;
- ``decay`` is one number a (slot, head): read from SMEM as a scalar;
- rule 1 of the state pool (kv_pool.StatePool): a slot whose ``active``
  flag is down keeps its state bit for bit. The flag is a scalar in
  SMEM and selects the branch: the tile is copied through, its inputs
  are never touched, so non-finite values in a riding row cannot reach
  it. Its ``y`` is zero.

The arithmetic per element is ``advance_state``'s, in float32; only the
sum over N is taken in another order.

:func:`supports` is the one predicate (shapes and dtypes only) that says
whether Mosaic's tiling takes a state pool; the serve step asks it when
the program is traced and keeps the plain body otherwise.

Runs compiled on TPU and in interpreter mode elsewhere. Tested against
the plain body in tests/test_ssm_state_step.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8     # float32 rows of a register
# A tile of state: in and out, each double-buffered, is four of them in
# VMEM. Smaller tiles pay more grid steps (1 MB: +3%), larger ones a
# longer unoverlapped first read and last write (4 MB: +1%).
_TILE_BYTES = 2 << 20
# Heads of a tile advanced in one trip of the kernel's loop. A head is a
# round trip through the MXU and the cross-lane unit, and the scheduler
# overlaps only what one trip holds: 2.02 ms a layer at one head a trip,
# 1.28 at two, 0.92 at four, 0.841 at eight, 0.835 with the tile's 64
# heads unrolled. But lowering the program costs by the operation: the 64
# unrolled heads added 3-4 s to every start of the serving process
# (PERF.md PR 34), so eight it is.
_HEADS_A_TRIP = 8


def supports(head_dim: int, state_dim: int, state_dtype) -> bool:
    """True when the kernel takes this state: a head's (head_dim, N) is
    whole float32 tiles. The twin of ``paged_attention.supports``."""
    return (state_dim % _LANES == 0 and head_dim % _SUBLANES == 0
            and jnp.dtype(state_dtype) == jnp.dtype(jnp.float32))


def _heads_per_tile(heads: int, groups: int, head_bytes: int) -> int:
    """The most heads of one group that fit ``_TILE_BYTES``, dividing
    the group's heads."""
    per = heads // groups
    hb = max(1, min(per, _TILE_BYTES // head_bytes))
    while per % hb:
        hb -= 1
    return hb


def _kernel(layer_ref, active_ref, decay_ref, dtx_ref, b_ref, c_ref,
            s_ref, y_ref, o_ref, *, hb: int):
    del layer_ref                       # the index maps read it
    s, t = pl.program_id(0), pl.program_id(1)

    @pl.when(active_ref[s] != 0)
    def _():
        b, c = b_ref[...], c_ref[...]               # (1, N)
        dtx = dtx_ref[0, 0]                         # (P, hb)
        head = lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
        ones = jnp.ones((hb, b.shape[1]), jnp.float32)
        trip = next(n for n in (_HEADS_A_TRIP, 4, 2, 1) if hb % n == 0)

        def heads(g, y):
            for h in (g * trip + j for j in range(trip)):
                # column h of dtx on every lane, exactly (module docstring)
                col = jnp.dot(jnp.where(head == h, dtx, 0.0), ones,
                              precision=lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # (P, N)
                new = decay_ref[s, t * hb + h] * s_ref[h] + col * b
                o_ref[h] = new
                y = jnp.where(head == h,
                              jnp.sum(new * c, axis=-1, keepdims=True), y)
            return y

        y_ref[0, 0] = lax.fori_loop(0, hb // trip, heads,
                                    jnp.zeros_like(dtx))

    @pl.when(active_ref[s] == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def _impl(pool, layer, active, decay, dtx, b, c, *, interpret):
    _, S, H, P, N = pool.shape
    G = b.shape[1]
    hb = _heads_per_tile(H, G, 4 * P * N)
    T, per = H // hb, H // G // hb       # tiles a slot, tiles a group
    # (S, H, P) -> (S, T, P, hb): a head's values down the sublanes
    dtx_t = dtx.reshape(S, T, hb, P).transpose(0, 1, 3, 2)
    tile = pl.BlockSpec((None, None, hb, P, N),
                        lambda s, t, layer, active: (layer[0], s, t, 0, 0))
    group = pl.BlockSpec((None, None, 1, N),
                         lambda s, t, layer, active: (s, t // per, 0, 0))
    column = pl.BlockSpec((1, 1, P, hb),
                          lambda s, t, layer, active: (s, t, 0, 0))
    y_t, pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, T),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      column, group, group, tile],
            out_specs=[column, tile]),
        out_shape=[jax.ShapeDtypeStruct((S, T, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the two scalar-prefetch ones: the pool is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * 4 * hb * P * N + (8 << 20)),
        name="ssm_state_step",
        interpret=interpret,
    )(layer, active, decay, dtx_t, b[:, :, None], c[:, :, None], pool)
    return y_t.transpose(0, 1, 3, 2).reshape(S, H, P), pool


def ssm_state_step(pool, decay, dtx, b, c, *, layer: int, active,
                   interpret: bool | None = None):
    """Advance state layer ``layer`` of ``pool`` by one token for the
    slots where ``active``, and read the token's ``y`` out of it.

    ``pool``: the WHOLE state pool (state_layers, S, heads, head_dim, N)
    float32, updated in place (donate it: nothing but the layer's tiles
    is written); ``layer``: which state layer (an operand, not a
    constant of the kernel); ``decay`` (S, heads), ``dtx`` (S, heads,
    head_dim) the token's ``dt * x``, ``b`` / ``c`` (S, groups, N), all
    float32; ``active`` (S,) bool. Returns (``y`` (S, heads, head_dim)
    float32, the pool). A slot that is not active keeps its state bit
    for bit whatever its row holds, and its ``y`` is zero."""
    if interpret is None:
        from tpu_ddp.ops.pallas import interpret_mode
        interpret = interpret_mode()
    _, S, H, P, N = pool.shape
    if not supports(P, N, pool.dtype):
        raise ValueError(
            f"ssm_state_step does not take head_dim={P}, state={N}, "
            f"pool dtype {pool.dtype} (see supports())")
    G = b.shape[1]
    if decay.shape != (S, H) or dtx.shape != (S, H, P) \
            or b.shape != (S, G, N) or c.shape != b.shape or H % G:
        raise ValueError(
            f"for a pool {pool.shape}: decay (S, heads), dtx (S, heads, "
            f"head_dim), b and c (S, groups, N); got {decay.shape}, "
            f"{dtx.shape}, {b.shape}, {c.shape}")
    f32 = jnp.float32
    return _impl(pool, jnp.full((1,), layer, jnp.int32),
                 active.astype(jnp.int32), decay.astype(f32),
                 dtx.astype(f32), b.astype(f32), c.astype(f32),
                 interpret=bool(interpret))
