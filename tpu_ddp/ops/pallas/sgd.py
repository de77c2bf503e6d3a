"""Fused SGD(momentum, weight-decay) update as a single Pallas kernel.

Replaces the elementwise chain of the torch-semantics update (reference
part1/main.py:124-125; tpu_ddp/ops/optim.py)::

    g   <- grad + wd * p
    buf <- mom * buf + g
    p   <- p - lr * buf

For each parameter leaf the whole chain runs in ONE VMEM-resident pass:
params, grads and momentum stream HBM->VMEM once, the new params and new
momentum stream back once — the minimum possible HBM traffic (the update is
purely memory-bound). Inputs are aliased to outputs so the update is
in-place in HBM (donated buffers, no allocation churn).

Leaves are flattened, zero-padded to a (rows, 128) lane layout and chunked
over a 1-D grid; padding lanes compute ``0 - lr*(mom*0 + 0 + wd*0) = 0`` so
they are exact no-ops and are sliced away on reshape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width is fixed at 128 on TPU; 512 sublanes x 128 lanes x 4 B = 256 KB
# per buffer block, x5 live buffers ~= 1.3 MB of VMEM — comfortably small.
_LANES = 128
_BLOCK_ROWS = 512


def _sgd_kernel(p_ref, g_ref, b_ref, new_p_ref, new_b_ref, *,
                lr: float, momentum: float, weight_decay: float):
    g = g_ref[:]
    if weight_decay:
        g = g + weight_decay * p_ref[:]
    buf = momentum * b_ref[:] + g
    new_b_ref[:] = buf
    new_p_ref[:] = p_ref[:] - lr * buf


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "weight_decay",
                                             "interpret"))
def _sgd_leaf(p2d, g2d, b2d, *, lr, momentum, weight_decay, interpret):
    rows = p2d.shape[0]
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    kernel = functools.partial(_sgd_kernel, lr=lr, momentum=momentum,
                               weight_decay=weight_decay)
    out_shape = jax.ShapeDtypeStruct(p2d.shape, p2d.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(out_shape, out_shape),
        input_output_aliases={0: 0, 2: 1},
        name="fused_sgd",
        interpret=interpret,
    )(p2d, g2d, b2d)


def _to_2d(x):
    """Flatten to (rows, 128) with zero padding; returns (x2d, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = -(-n // _LANES)
    pad = rows * _LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, _LANES), n


def fused_sgd_step(params, grads, momentum_buf, *, lr: float,
                   momentum: float, weight_decay: float,
                   interpret: bool | None = None):
    """Apply the fused update to every leaf of a parameter pytree.

    Returns ``(new_params, new_momentum_buf)`` with identical pytree
    structure. Numerics match :class:`tpu_ddp.ops.optim.SGD` exactly
    (tested leaf-wise in tests/test_pallas.py).
    """
    if interpret is None:
        from tpu_ddp.ops.pallas import interpret_mode
        interpret = interpret_mode()

    def leaf(p, g, b):
        shape = p.shape
        p2d, n = _to_2d(p)
        g2d, _ = _to_2d(g.astype(p.dtype))
        b2d, _ = _to_2d(b)
        np2d, nb2d = _sgd_leaf(p2d, g2d, b2d, lr=lr, momentum=momentum,
                               weight_decay=weight_decay,
                               interpret=interpret)
        return (np2d.reshape(-1)[:n].reshape(shape),
                nb2d.reshape(-1)[:n].reshape(shape))

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_b = treedef.flatten_up_to(momentum_buf)
    out = [leaf(p, g, b) for p, g, b in zip(flat_p, flat_g, flat_b)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_b = treedef.unflatten([o[1] for o in out])
    return new_p, new_b
