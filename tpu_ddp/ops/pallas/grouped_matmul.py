"""Grouped matrix product over sorted rows — Pallas TPU kernel.

The dropless expert layer (tpu_ddp/parallel/moe.py ``dropless_moe``)
sorts its ``T * top_k`` assignments by expert and multiplies each
expert's rows by that expert's matrix, twice a layer:

    out[offs[g]:offs[g+1]] = lhs[offs[g]:offs[g+1]] @ rhs[g]
    offs = cumsum(group_sizes);  out[offs[-1]:] = 0

``lax.ragged_dot`` is the definition this kernel is tested against (it
leaves the rows past the last group undefined; here they are zeros).
At serving sizes a group holds tens of rows, so the work is the weights:
453 MB and 226 MB a layer at the benchmark's geometry (36 held experts,
4096 x 1536 and 768 x 4096 bf16) for 640 or 2,560 rows of which half
are in a group. Any implementation reads every held expert's matrix
once; the rows are small beside them.

TPU mapping:
- ``lhs`` lies WHOLE in on-chip memory for the call (640 x 4096 bf16 is
  5 MB, 2,560 x 4096 21 MB of the v5e's 128 MiB): the kernel never
  fetches a row twice and needs no row-tile schedule;
- grid ``(column tiles, groups)``, groups innermost: each step streams
  one ``(k, tn)`` tile of one expert's matrix through the pipeline's
  double buffers, so every weight byte crosses HBM once and nothing
  else does (no ``k`` axis: a tile holds the whole contraction, so there
  is no accumulator to carry between steps);
- the output block is all ``m`` rows of the column tile, resident
  across the groups, zeroed at the first: rows past the last group are
  zeros because nothing else writes them;
- the group offsets are a scalar-prefetch operand. A step multiplies
  the ``tm`` rows from the group's first row, rounded down to a packed
  bf16 register (16 rows), by the tile, and stores the rows that are
  the group's through a row mask; a group of more rows takes more trips
  of a loop whose count is read from the offsets (an empty group takes
  none). The MXU is loaded with the weights once a trip whatever
  ``tm`` is, so a trip costs what the 128-row MXU pass costs and ``tm``
  is simply that (or all the rows, where they are fewer).

Read on the v5e (PERF.md PR 37, host clock a call, one process): at the
four problems the kernel takes the time of its own stream with every
group empty (0.63 / 0.34 ms at 640 rows, 0.67 / 0.39 at 2,560, whatever
the tile's width from 1 to 6 MB and for 32, 64 or 128 rows a trip; 256
rows a trip 5-10% more), where ``lax.ragged_dot`` took 0.87 / 0.61 and
1.63 / 1.01 and the library's ``megablox.gmm`` at its best tilings
(``tk = k``) 0.66 / 0.35 and 0.76 / 0.41. In the serving programs
0.60 / 0.30 and 0.61 / 0.32 ms of device time a call. No shape lost to
``ragged_dot``, so ``supports`` has no row count in it: it says, from
shapes and dtypes alone, where the kernel CAN take the product;
elsewhere ``dropless_moe`` keeps ``lax.ragged_dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_ALIGN = 16                 # rows of one packed bf16 register
_ROW_TILE = 128                 # rows a trip: one pass of the MXU
_TILE_BYTES = 2 << 20           # one (k, tn) weight tile
_VMEM_BYTES = 64 << 20          # of the v5e's 128 MiB
_VMEM_SPARE = 8 << 20           # the compiler's own, beside the buffers


def _column_tile(k: int, n: int) -> int:
    """Widest multiple of 128 that divides ``n`` and keeps a ``(k, tn)``
    bf16 tile within ``_TILE_BYTES`` (0: not even 128 columns fit)."""
    fit = [tn for tn in range(128, n + 1, 128)
           if n % tn == 0 and 2 * k * tn <= _TILE_BYTES]
    return max(fit, default=0)


def _vmem_bytes(m: int, k: int, tn: int) -> int:
    """lhs once, the weight tile and the output block twice."""
    return 2 * m * k + 2 * 2 * k * tn + 2 * 4 * m * tn


def supports(m: int, k: int, n: int, lhs_dtype, rhs_dtype) -> bool:
    """Does the kernel take ``(m, k) @ (groups, k, n)``? Shapes and
    dtypes only: bf16 operands (float32 out), ``k`` and ``n`` whole
    lanes, ``m`` whole packed registers, and the rows resident on chip
    beside a weight tile and an output block."""
    if jnp.dtype(lhs_dtype) != jnp.bfloat16 \
            or jnp.dtype(rhs_dtype) != jnp.bfloat16:
        return False
    if k % 128 or n % 128 or m % _ROW_ALIGN or not m:
        return False
    tn = _column_tile(k, n)
    return bool(tn) and _vmem_bytes(m, k, tn) + _VMEM_SPARE <= _VMEM_BYTES


def _kernel(offs_ref, lhs_ref, rhs_ref, out_ref, *, tm):
    g = pl.program_id(1)
    m = lhs_ref.shape[0]

    @pl.when(g == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, end = offs_ref[g], offs_ref[g + 1]
    first = start // _ROW_ALIGN * _ROW_ALIGN
    trips = jnp.where(end > start, (end - first + tm - 1) // tm, 0)

    def trip(i, carry):
        # the last window is pulled back inside the rows; the mask is on
        # absolute rows, so it still stores exactly the group's
        row0 = pl.multiple_of(jnp.minimum(first + i * tm, m - tm),
                              _ROW_ALIGN)
        y = jnp.dot(lhs_ref[pl.ds(row0, tm), :], rhs_ref[...],
                    preferred_element_type=jnp.float32)
        row = row0 + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= start) & (row < end)
        out_ref[pl.ds(row0, tm), :] = jnp.where(
            mine, y, out_ref[pl.ds(row0, tm), :])
        return carry

    lax.fori_loop(0, trips, trip, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _impl(lhs, rhs, group_sizes, *, interpret):
    m, k = lhs.shape
    groups, _, n = rhs.shape
    tn = _column_tile(k, n)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes, dtype=jnp.int32)])
    return pl.pallas_call(
        functools.partial(_kernel, tm=min(_ROW_TILE, m)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, groups),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec((None, k, tn),
                                   lambda j, g, offs: (g, 0, j))],
            out_specs=pl.BlockSpec((m, tn), lambda j, g, offs: (0, j))),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(m, k, tn) + _VMEM_SPARE),
        name="grouped_matmul",
        interpret=interpret,
    )(offs, lhs, rhs)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (m, k) bf16 rows sorted by group, ``rhs`` (groups, k, n)
    bf16, ``group_sizes`` (groups,) int32 with ``sum <= m``: returns
    (m, n) float32, row ``r`` of group ``g`` being ``lhs[r] @ rhs[g]``
    summed in float32, and the rows past the last group zeros. Raises
    for a shape ``supports`` refuses. Compiled on the TPU, interpreted
    elsewhere."""
    from tpu_ddp.ops.pallas import interpret_mode
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"lhs (m, k), rhs (groups, k, n), group_sizes (groups,); got "
            f"{lhs.shape}, {rhs.shape}, {group_sizes.shape}")
    m, k = lhs.shape
    n = rhs.shape[2]
    if not supports(m, k, n, lhs.dtype, rhs.dtype):
        raise ValueError(
            f"grouped_matmul does not take ({m}, {k}) {lhs.dtype} @ "
            f"(groups, {k}, {n}) {rhs.dtype} (see supports())")
    return _impl(lhs, rhs, group_sizes.astype(jnp.int32),
                 interpret=interpret_mode())
