"""2-process MPMD pipeline drill — per-stage programs over socket edges.

Each process IS one pipeline slice: it compiles only its stage's
forward/backward (parallel/mpmd.py StageProgram), holds only its
stage's params + optimizer state, and exchanges activations/cotangents
with its peer over a TCP socket edge carrying the round-7 wire formats
(the DCN stand-in). No jax.distributed, no collectives — the edge IS
the only communication, which is the whole point of the MPMD model.

Honours the reference launch contract so the cluster launcher can
spawn it::

    python -m tpu_ddp.launch examples/mpmd_train.py --nproc 2

Env knobs: TPU_DDP_MPMD_STEPS (default 4), TPU_DDP_MPMD_COMPRESS
(none|bf16|int8|int8-noef — the CROSS-SLICE edge wire format; default
bf16), TPU_DDP_MPMD_MICRO (microbatches, default 4), TPU_DDP_LM_PRESET.

Exit contract (tests/test_mpmd.py's slow drill asserts it): exit 0
with a final ``[mpmd] RESULT ...`` line on the last stage showing the
loss decreased and the edge compression ratio matched the wire format;
exit 1 otherwise.
"""

import os
import socket
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "parts"))

from common import parse_arguments  # noqa: E402

PP = 2  # two processes, one stage each


def _connect(rank: int, ip: str, port: int) -> socket.socket:
    """Stage 1 listens, stage 0 dials (with retry — the launcher gives
    no start-order guarantee). One TCP connection, full duplex: the
    down edge (activations) and up edge (cotangents) share it."""
    if rank == 1:
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("0.0.0.0", port))
        srv.listen(1)
        conn, _ = srv.accept()
        srv.close()
        return conn
    deadline = time.time() + 60
    while True:
        try:
            return socket.create_connection((ip, port), timeout=5)
        except OSError:
            if time.time() > deadline:
                raise
            time.sleep(0.2)


def main(argv=None) -> int:
    args = parse_arguments(argv, require_num_nodes=True)
    if args.num_nodes != PP:
        raise SystemExit(f"mpmd_train is a {PP}-process drill "
                         f"(got --num-nodes {args.num_nodes})")
    rank = args.rank if args.rank is not None else 0

    import jax

    from tpu_ddp.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from tpu_ddp.models import make_transformer
    from tpu_ddp.ops.optim import SGD
    from tpu_ddp.parallel.compress import EdgeCodec
    from tpu_ddp.parallel.mpmd import (MPMDPipeline, SliceTopology,
                                       SocketEdge, split_stage_params)
    from tpu_ddp.parallel.pipeline import stack_block_params
    from tpu_ddp.train.pipeline import StageScheduler

    steps = int(os.environ.get("TPU_DDP_MPMD_STEPS", "4"))
    spec = os.environ.get("TPU_DDP_MPMD_COMPRESS", "bf16")
    num_micro = int(os.environ.get("TPU_DDP_MPMD_MICRO", "4"))
    preset = os.environ.get("TPU_DDP_LM_PRESET", "TransformerLM-tiny")
    seq_len = 32
    batch = 2 * num_micro

    model = make_transformer(preset, max_seq_len=seq_len,
                             compute_dtype=np.float32)
    # Both processes derive the SAME init from the same seed, then keep
    # only their stage's partition — no broadcast needed.
    params = stack_block_params(model.init(jax.random.key(0)))
    params_s = split_stage_params(params, PP)[rank]

    # The edge: both directions over one socket; each process owns the
    # codec of its SENDING direction (error-feedback residuals are
    # sender state). The two stages are two "slices" here, so the one
    # boundary is cross-slice and carries the compressed format.
    sock = _connect(rank, args.master_ip, int(args.master_port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    edge = SocketEdge(sock, EdgeCodec(spec, seed=rank))

    sched = StageScheduler(PP, depth=2)
    pipe = MPMDPipeline(model, PP, seq_len, num_micro=num_micro,
                        topology=SliceTopology.even(PP, PP),
                        compress=spec, scheduler=sched)

    rng = np.random.default_rng(1234)
    tokens = rng.integers(0, model.vocab_size,
                          size=(batch, seq_len + 1)).astype(np.int32)
    x, y = tokens[:, :-1], tokens[:, 1:]
    mb = batch // num_micro
    micro = x.reshape(num_micro, mb, seq_len)
    tmicro = y.reshape(num_micro, mb, seq_len)
    denom = float(batch * seq_len)

    opt = SGD(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params_s)
    losses = []
    for step in range(steps):
        if rank == 0:
            grads, _ = pipe.run_stage(0, params_s, micro, None,
                                      None, edge, edge, None)
        else:
            grads, loss_sum = pipe.run_stage(1, params_s, None, tmicro,
                                             edge, None, None, edge)
            losses.append(float(np.asarray(loss_sum)) / denom)
            print(f"[mpmd] rank={rank} step {step + 1}/{steps} "
                  f"loss {losses[-1]:.4f}", flush=True)
        grads = jax.tree.map(
            lambda g: g.astype(jnp.float32) / denom, grads)
        params_s, opt_state = opt.apply(params_s, grads, opt_state)
        sched.step_done(step)

    stats = edge.stats()
    print(f"[mpmd] rank={rank} edge {stats}", flush=True)
    print(f"[mpmd] rank={rank} sched "
          f"{sched.stats()['stages'][rank]}", flush=True)
    sock.close()
    if rank == 1:
        want = {"none": 1.0, "bf16": 1.9, "int8": 3.5,
                "int8-noef": 3.5}[spec]
        ok = losses[-1] < losses[0] and stats["ratio"] >= want
        print(f"[mpmd] RESULT loss {losses[0]:.4f}->{losses[-1]:.4f} "
              f"ratio {stats['ratio']} ({spec}) "
              f"{'OK' if ok else 'FAIL'}", flush=True)
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
