"""Collective-communication microbenchmarks over the device mesh.

The reference's performance story hinges on its gradient-sync collectives
(gather/scatter vs ring all-reduce vs bucketed DDP — SURVEY.md §6 shows
the ladder's speedups are entirely comm-bound), but it ships no way to
measure the primitives themselves. This module does: it times each XLA
collective the framework's strategies are built from (``psum``,
``psum_scatter``, ``all_gather``, ``ppermute`` ring hop, ``all_to_all``)
over an actual mesh axis, so regressions in the comm layer show up as
numbers rather than as mysterious step-time drift.

Usage::

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.utils.collectives import bench_collectives
    print(bench_collectives(make_mesh(), mb=8))

Each op runs inside one jitted ``shard_map`` over the ``dp`` axis, is
compiled + warmed once, then timed over ``iters`` runs with
``block_until_ready`` (the same discipline as the train-step timing
harness, tpu_ddp/utils/timing.py). Reported bandwidth is the algorithmic
per-device payload divided by wall time — comparable across ops, not a
hardware line rate.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_ddp.parallel.mesh import DATA_AXIS


def _ops(axis: str, n: int):
    ring = [(i, (i + 1) % n) for i in range(n)]
    return {
        "psum": lambda x: lax.psum(x, axis),
        "psum_scatter": lambda x: lax.psum_scatter(
            x.reshape(n, -1), axis, scatter_dimension=0),
        "all_gather": lambda x: lax.all_gather(x, axis, tiled=True),
        "ppermute": lambda x: lax.ppermute(x, axis, ring),
        "all_to_all": lambda x: lax.all_to_all(
            x.reshape(n, -1), axis, split_axis=0, concat_axis=0,
            tiled=False),
    }


def bench_collectives(mesh: Mesh, mb: float = 4.0, iters: int = 10,
                      axis: str = DATA_AXIS,
                      dtype: str = "float32") -> dict:
    """Time each collective on ``mesh``'s ``axis``; returns a dict
    ``{op: {"ms": avg_ms, "gbps": payload_gb_per_s}}``.

    ``mb`` is the per-device payload in MiB of ``dtype`` — the element
    count scales with the itemsize, so ``dtype="int8"`` times the same
    BYTES through 4x the elements, which is exactly the compressed-wire
    question (parallel/compress.py ships gradients as s8/u16): does the
    fabric move reduced-dtype payloads at the same line rate? Bandwidth
    is computed from the actual itemsize. Integer dtypes skip nothing:
    psum/psum_scatter reduce integers exactly. Runs anywhere a mesh
    exists — on the virtual CPU mesh the numbers are only useful
    relative to each other; on real chips they expose the ICI.
    """
    n = mesh.shape[axis]
    if n < 2:
        raise ValueError(f"axis {axis!r} has size {n}; need >= 2 devices "
                         "to move bytes")
    # jnp resolves names numpy alone does not know (e.g. "bfloat16").
    dt = jnp.dtype(dtype)
    itemsize = dt.itemsize
    n_elems = int(mb * (1 << 20) / itemsize)
    n_elems -= n_elems % n  # divisible for the reshaping ops
    bytes_payload = n_elems * itemsize

    rng = np.random.default_rng(0)
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        host = rng.integers(info.min, info.max + 1, size=(n * n_elems,)) \
            .astype(dt)
    else:
        host = rng.normal(size=(n * n_elems,)).astype(dt)
    # Shard the payload over the SAME axis the collectives run on
    # (other mesh axes replicate), or the measurement is meaningless.
    x = jax.device_put(host, NamedSharding(mesh, P(axis)))

    results = {}
    for name, op in _ops(axis, n).items():
        fn = jax.jit(jax.shard_map(
            op, mesh=mesh, in_specs=P(axis),
            out_specs=P(axis), check_vma=False))
        jax.block_until_ready(fn(x))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        results[name] = {
            "ms": round(dt * 1e3, 4),
            "gbps": round(bytes_payload / dt / 1e9, 3),
        }
    return results


def main(argv=None) -> int:
    import argparse
    import json

    from tpu_ddp.parallel.mesh import make_mesh

    ap = argparse.ArgumentParser(
        description="microbenchmark XLA collectives over the dp axis")
    ap.add_argument("--mb", type=float, default=4.0,
                    help="per-device payload in MiB")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="float32",
                    help="payload dtype (float32, bfloat16, int8, ... — "
                         "compressed-wire microbenchmarks)")
    args = ap.parse_args(argv)
    mesh = make_mesh()
    out = {"devices": int(np.prod(list(mesh.shape.values()))),
           "platform": jax.devices()[0].platform,
           "payload_mib": args.mb,
           "dtype": args.dtype,
           "collectives": bench_collectives(mesh, args.mb, args.iters,
                                            dtype=args.dtype)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
