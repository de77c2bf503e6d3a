"""MPMD pipeline: per-stage compiled programs over explicit edges.

The SPMD engines in ``parallel/pipeline.py`` express the pipeline as ONE
jitted program on one mesh — every device holds every stage's code, and
stage selection happens with ``lax.axis_index`` inside shard_map. That
is the right shape inside a slice (ICI-dense, one compiler view of the
whole step) and the wrong one across slices: a multi-slice pipeline
(PAPERS.md: *Scaling Deep Learning Training with MPMD Pipeline
Parallelism*, arXiv 2412.14374) wants each slice to compile ONLY its
stages' forward/backward against only its stages' params, with
activations and cotangents crossing slice boundaries as explicit DCN
transfers, not as ring collectives of a global program.

This module is that scale-out path:

- :class:`StageProgram` — one stage's jit-compiled forward/backward
  pair. Stage 0 owns the embedding, the last stage owns ln_f + head,
  every stage owns its contiguous slice of transformer blocks. The
  backward recomputes the stage forward under ``jax.vjp`` from the
  saved input (the same recompute trade as the SPMD 1F1B), so a stage
  keeps O(pp) saved inputs, never activations.
- :class:`InProcessEdge` / :class:`SocketEdge` — directed stage-to-stage
  channels. In-process edges back the CPU/test path and the intra-slice
  hops (``jax.device_put`` is the transport, a deque the buffer);
  socket edges back the multi-process drill (examples/mpmd_train.py),
  pickled numpy wires over TCP. Every edge owns an
  :class:`~tpu_ddp.parallel.compress.EdgeCodec`: fp32 on intra-slice
  hops, the round-7 bf16/int8(+error-feedback) wire formats on
  cross-slice hops — the DCN is the slow wire, so that is where the
  bytes matter (:class:`SliceTopology` decides which is which).
- :class:`MPMDPipeline` — the host-driven 1F1B loop over per-stage
  programs. The host owns the schedule (tick -> (stage, fwd mb, bwd
  mb)); JAX's async dispatch keeps stages' compute in flight while the
  host shuffles edge payloads, and a
  :class:`~tpu_ddp.train.pipeline.StageScheduler` accounts each
  stage's warmup/steady/cooldown ticks and bounds its in-flight window.
  Guard-skip stays host-side here: a non-finite loss skips the whole
  update (params untouched), mirroring the jit-side
  ``select_update`` contract of the SPMD rungs.

Numerics contract: with fp32 edges the MPMD step computes EXACTLY the
dense model's loss and gradients (tests/test_mpmd.py pins it against
the dense trainer the same way the SPMD schedules are pinned); with
compressed cross-slice edges the per-step gradient is lossy but the
error-feedback residual keeps the trajectory within the acceptance
envelope (scripts/bench_pipeline_schedules.py measures it).

Dropout is out of scope on this path (MPMD serves the scale-out bench
and drills; the SPMD engines carry the regularization story) — a model
with ``dropout_rate > 0`` is rejected at construction.
"""

from __future__ import annotations

import dataclasses
import pickle
import socket
import struct
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpu_ddp.parallel.compress import EdgeCodec

__all__ = [
    "SliceTopology", "StageProgram", "InProcessEdge", "SocketEdge",
    "MPMDPipeline", "split_stage_params", "merge_stage_grads",
    "spmd_pipeline_hlo", "mega_edge_hlo",
]


# ---------------------------------------------------------------------------
# Topology: which stages live on which slice.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SliceTopology:
    """Stage -> slice assignment; decides which edges cross DCN.

    ``stage_slice[s]`` is the slice id hosting stage ``s``. The edge
    ``s -> s+1`` is *cross-slice* iff the two ids differ — those edges
    get the compressed wire format, intra-slice edges stay fp32.
    """

    stage_slice: tuple

    def __post_init__(self):
        if not self.stage_slice:
            raise ValueError("empty topology")
        ids = list(self.stage_slice)
        if ids != sorted(ids):
            raise ValueError(
                f"stages must map to slices in order, got {ids}")

    @classmethod
    def single_slice(cls, pp_size: int) -> "SliceTopology":
        return cls(tuple(0 for _ in range(pp_size)))

    @classmethod
    def even(cls, pp_size: int, num_slices: int) -> "SliceTopology":
        """Contiguous stages split evenly over ``num_slices``."""
        if pp_size % num_slices:
            raise ValueError(f"pp={pp_size} not divisible by "
                             f"num_slices={num_slices}")
        per = pp_size // num_slices
        return cls(tuple(s // per for s in range(pp_size)))

    @property
    def pp_size(self) -> int:
        return len(self.stage_slice)

    def is_cross(self, boundary: int) -> bool:
        """True when edge ``boundary -> boundary+1`` crosses slices."""
        return (self.stage_slice[boundary]
                != self.stage_slice[boundary + 1])

    def cross_boundaries(self) -> list:
        return [b for b in range(self.pp_size - 1) if self.is_cross(b)]


# ---------------------------------------------------------------------------
# Per-stage parameter partition (linear stage layout).
# ---------------------------------------------------------------------------


def split_stage_params(params: dict, pp_size: int) -> list:
    """Stacked-param tree -> per-stage param dicts.

    Stage s owns block rows ``[s*Lps, (s+1)*Lps)``; stage 0 additionally
    owns ``embed``, the last stage ``ln_f`` + ``head``. Each returned
    dict references ONLY its stage's arrays — the property per-stage
    compilation exists for.
    """
    L = jax.tree.leaves(params["blocks"])[0].shape[0]
    if L % pp_size:
        raise ValueError(f"{L} layers not divisible by pp={pp_size}")
    lps = L // pp_size
    out = []
    for s in range(pp_size):
        p = {"blocks": jax.tree.map(
            lambda x: x[s * lps:(s + 1) * lps], params["blocks"])}
        if s == 0:
            p["embed"] = params["embed"]
        if s == pp_size - 1:
            p["ln_f"] = params["ln_f"]
            p["head"] = params["head"]
        out.append(p)
    return out


def merge_stage_grads(stage_grads: list) -> dict:
    """Inverse of :func:`split_stage_params` for gradient trees."""
    blocks = jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0),
        *[g["blocks"] for g in stage_grads])
    return {"embed": stage_grads[0]["embed"],
            "ln_f": stage_grads[-1]["ln_f"],
            "head": stage_grads[-1]["head"],
            "blocks": blocks}


# ---------------------------------------------------------------------------
# One stage's compiled programs.
# ---------------------------------------------------------------------------


class StageProgram:
    """Forward/backward jit pair for ONE pipeline stage.

    Four distinct compiled programs exist across a pipeline (first /
    middle / last stage shapes), each closed over only its stage's
    param structure — ``jit`` here is per-stage compilation, not a
    slice of a global program. Dropout keys would need the global layer
    index; the MPMD path runs eval-mode trunks (module docstring).
    """

    def __init__(self, model, stage: int, pp_size: int, seq_len: int):
        if pp_size < 2:
            raise ValueError("MPMD needs pp_size >= 2 (one stage is "
                             "just the dense model)")
        if model.dropout_rate > 0.0:
            raise ValueError("MPMD path does not support dropout; "
                             "use the SPMD schedules for regularized "
                             "training")
        model.check_seq_len(seq_len)
        self.model = model
        self.stage = stage
        self.pp_size = pp_size
        self.is_first = stage == 0
        self.is_last = stage == pp_size - 1
        pos = model._positions(seq_len)
        cd = model.compute_dtype

        def run_blocks(blocks, x):
            def body(h, layer):
                h, _ = model.block_apply_aux(layer, h, pos, None)
                return h, None
            h, _ = jax.lax.scan(body, x, blocks)
            return h

        def fwd_first(p, toks):
            x = p["embed"][toks].astype(cd)
            return run_blocks(p["blocks"], x)

        def fwd_mid(p, x):
            return run_blocks(p["blocks"], x.astype(cd))

        def loss_last(p, x, tgt):
            from tpu_ddp.ops.loss import softmax_cross_entropy
            y = run_blocks(p["blocks"], x.astype(cd))
            logits = self.model.head_apply(
                {"ln_f": p["ln_f"], "head": p["head"]}, y)
            nll = softmax_cross_entropy(
                logits.reshape(-1, logits.shape[-1]), tgt.reshape(-1))
            return jnp.sum(nll)

        if self.is_last:
            def bwd_last(p, x, tgt):
                (loss, (gp, dx)) = jax.value_and_grad(
                    loss_last, argnums=(0, 1))(p, x, tgt)
                return loss, gp, dx.astype(jnp.float32)
            self.bwd = jax.jit(bwd_last)
            self.fwd = None
        elif self.is_first:
            def bwd_first(p, toks, dy):
                _, vjp = jax.vjp(lambda q: fwd_first(q, toks), p)
                (gp,) = vjp(dy.astype(cd))
                return gp
            self.fwd = jax.jit(fwd_first)
            self.bwd = jax.jit(bwd_first)
        else:
            def bwd_mid(p, x, dy):
                _, vjp = jax.vjp(fwd_mid, p, x)
                gp, dx = vjp(dy.astype(cd))
                return gp, dx.astype(jnp.float32)
            self.fwd = jax.jit(fwd_mid)
            self.bwd = jax.jit(bwd_mid)


# ---------------------------------------------------------------------------
# Edges.
# ---------------------------------------------------------------------------


class InProcessEdge:
    """Directed stage channel inside one process.

    ``jax.device_put`` of the decoded payload is the transfer; the wire
    format still round-trips through the codec, so the compression
    numerics and the byte accounting are identical to the socket path
    (what tier-1 tests, the drill then exercises over real sockets).
    """

    def __init__(self, codec: EdgeCodec | None = None, device=None):
        self.codec = codec or EdgeCodec("none")
        self.device = device
        self._q: deque = deque()
        self.messages = 0

    def send(self, x) -> None:
        wire, _ = self.codec.encode(x)
        self._q.append(wire)
        self.messages += 1

    def recv(self):
        out = EdgeCodec.decode(self._q.popleft())
        if self.device is not None:
            out = jax.device_put(out, self.device)
        return out

    def __len__(self) -> int:
        return len(self._q)

    def stats(self) -> dict:
        return {"transport": type(self).__name__,
                "spec": self.codec.spec,
                "messages": self.messages,
                "wire_bytes": int(self.codec.bytes_sent),
                "dense_bytes": int(self.codec.bytes_dense),
                "ratio": round(self.codec.ratio, 3)}


class SocketEdge(InProcessEdge):
    """Stage channel over a connected TCP socket (the 2-process drill).

    Wire = 4-byte big-endian length + pickled dict of numpy arrays.
    One SocketEdge end sends, the peer's receives — construct a pair
    per direction. Blocking recv IS the schedule synchronization: a
    stage that needs an activation that has not arrived simply waits,
    which is exactly the 1F1B dependence order.
    """

    def __init__(self, sock: socket.socket,
                 codec: EdgeCodec | None = None, device=None):
        super().__init__(codec, device)
        self.sock = sock

    def send(self, x) -> None:
        wire, _ = self.codec.encode(x)
        host = {k: (np.asarray(v) if hasattr(v, "shape") else v)
                for k, v in wire.items()}
        blob = pickle.dumps(host, protocol=pickle.HIGHEST_PROTOCOL)
        self.sock.sendall(struct.pack(">I", len(blob)) + blob)
        self.messages += 1

    def _read_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("edge peer closed")
            buf += chunk
        return buf

    def recv(self):
        (n,) = struct.unpack(">I", self._read_exact(4))
        wire = pickle.loads(self._read_exact(n))
        out = EdgeCodec.decode(wire)
        if self.device is not None:
            out = jax.device_put(out, self.device)
        return out


def build_edges(topology: SliceTopology, compress: str = "bf16",
                block_size: int = 256, devices=None) -> tuple:
    """(down, up) edge lists for an in-process pipeline.

    ``down[b]`` carries activations over boundary ``b`` (stage b ->
    b+1), ``up[b]`` cotangents back. Cross-slice boundaries get the
    ``compress`` wire format (each DIRECTION carries its own codec —
    error-feedback residuals are per-edge sender state); intra-slice
    boundaries stay fp32.
    """
    down, up = [], []
    for b in range(topology.pp_size - 1):
        spec = compress if topology.is_cross(b) else "none"
        dev_fwd = devices[b + 1] if devices is not None else None
        dev_bwd = devices[b] if devices is not None else None
        down.append(InProcessEdge(EdgeCodec(spec, block_size, seed=2 * b),
                                  device=dev_fwd))
        up.append(InProcessEdge(EdgeCodec(spec, block_size,
                                          seed=2 * b + 1),
                                device=dev_bwd))
    return down, up


# ---------------------------------------------------------------------------
# The host-driven 1F1B engine.
# ---------------------------------------------------------------------------


class MPMDPipeline:
    """All stages of an MPMD pipeline driven by one host loop.

    The single-process form (every ``StageProgram`` in this process,
    edges in-process) is the CPU/test path AND the template for the
    per-process form: :meth:`run_stage` executes ONE stage's tick loop
    against whatever edges it is handed, so a multi-process launch
    simply runs ``run_stage`` once per process with socket edges
    (examples/mpmd_train.py).
    """

    def __init__(self, model, pp_size: int, seq_len: int, *,
                 num_micro: int | None = None,
                 topology: SliceTopology | None = None,
                 compress: str = "bf16", block_size: int = 256,
                 optimizer=None, scheduler=None, devices=None):
        from tpu_ddp.ops.optim import SGD
        self.model = model
        self.pp_size = pp_size
        self.num_micro = num_micro if num_micro is not None else pp_size
        self.seq_len = seq_len
        self.topology = topology or SliceTopology.single_slice(pp_size)
        if self.topology.pp_size != pp_size:
            raise ValueError(
                f"topology covers {self.topology.pp_size} stages, "
                f"pipeline has {pp_size}")
        self.programs = [StageProgram(model, s, pp_size, seq_len)
                         for s in range(pp_size)]
        self.down, self.up = build_edges(self.topology, compress,
                                         block_size, devices=devices)
        self.optimizer = optimizer or SGD(learning_rate=0.1)
        self.scheduler = scheduler
        self.skipped_steps = 0
        # Test seam for the chaos drills: maps the harvested loss to
        # what the guard sees (inject NaN without breaking the math).
        self._chaos_hook: Callable[[float, int], float] | None = None
        self._step = 0

    # ---- schedule ------------------------------------------------------

    def ticks(self) -> int:
        return self.num_micro + 2 * (self.pp_size - 1)

    def run_stage(self, stage: int, params_s, micro_in, micro_tgt,
                  down_in, down_out, up_in, up_out) -> tuple:
        """One stage's full 1F1B tick loop; returns
        ``(grads_s, loss_sum)`` (loss_sum is 0.0 except on the last
        stage). ``micro_in``/``micro_tgt`` are the (M, mb, L) token /
        target arrays (first / last stage only); the four edge ends are
        whichever of this stage's channels exist (None at the pipeline
        ends).

        At tick t stage s forwards microbatch ``f = t - s`` and
        backwards ``b = t - 2(pp-1) + s`` — the same clocks as the SPMD
        1F1B — except the last stage fuses its forward+backward into
        one ``value_and_grad`` program (its f and b coincide).
        """
        S, M = self.pp_size, self.num_micro
        prog = self.programs[stage]
        saved: deque = deque()
        grads = None
        loss_sum = jnp.float32(0.0)
        sched = self.scheduler
        for t in range(self.ticks()):
            f = t - stage
            b = t - 2 * (S - 1) + stage
            f_valid = 0 <= f < M
            b_valid = 0 <= b < M
            did = False
            if prog.is_last:
                # forward+backward fused; f == b at the last stage
                if f_valid:
                    x = down_in.recv()
                    loss, gp, dx = prog.bwd(params_s, x,
                                            micro_tgt[f])
                    loss_sum = loss_sum + loss
                    grads = _tree_add(grads, gp)
                    up_out.send(dx)
                    did = True
            else:
                if f_valid:
                    if prog.is_first:
                        x = micro_in[f]
                    else:
                        x = down_in.recv()
                    saved.append(x)
                    down_out.send(prog.fwd(params_s, x))
                    did = True
                if b_valid:
                    dy = up_in.recv()
                    x = saved.popleft()
                    if prog.is_first:
                        gp = prog.bwd(params_s, x, dy)
                    else:
                        gp, dx = prog.bwd(params_s, x, dy)
                        up_out.send(dx)
                    grads = _tree_add(grads, gp)
                    did = True
            if sched is not None:
                sched.tick(stage, fwd=f_valid, bwd=b_valid,
                           handle=(jax.tree.leaves(grads)[0]
                                   if did and grads is not None
                                   else None))
        return grads, loss_sum

    # ---- single-process step ------------------------------------------

    def step_grads(self, params: dict, inputs, targets) -> tuple:
        """One MPMD step's (mean_loss, merged_grads) for a (B, L) batch.

        Stages interleave through the host loop: each tick touches
        every stage once (ascending), edges are FIFO, so the dataflow
        is identical to S concurrent per-process loops — just easier
        to test. Gradients come back in the dense model's layout,
        scaled to the mean-NLL normalization the dense trainer uses.
        """
        B, L = inputs.shape
        if B % self.num_micro:
            raise ValueError(f"batch {B} not divisible by "
                             f"num_micro={self.num_micro}")
        mb = B // self.num_micro
        micro = np.asarray(inputs, np.int32).reshape(
            self.num_micro, mb, L)
        tmicro = np.asarray(targets, np.int32).reshape(
            self.num_micro, mb, L)
        stage_params = split_stage_params(params, self.pp_size)

        S, M = self.pp_size, self.num_micro
        saved = [deque() for _ in range(S)]
        grads: list = [None] * S
        loss_sum = jnp.float32(0.0)
        sched = self.scheduler
        for t in range(self.ticks()):
            for s in range(S):
                prog = self.programs[s]
                f = t - s
                b = t - 2 * (S - 1) + s
                f_valid = 0 <= f < M
                b_valid = 0 <= b < M
                if prog.is_last:
                    if f_valid:
                        x = self.down[s - 1].recv() if s else micro[f]
                        loss, gp, dx = prog.bwd(stage_params[s], x,
                                                tmicro[f])
                        loss_sum = loss_sum + loss
                        grads[s] = _tree_add(grads[s], gp)
                        if s:
                            self.up[s - 1].send(dx)
                else:
                    if f_valid:
                        x = self.down[s - 1].recv() if s else micro[f]
                        saved[s].append(x)
                        self.down[s].send(
                            prog.fwd(stage_params[s], x))
                    if b_valid:
                        dy = self.up[s].recv()
                        x = saved[s].popleft()
                        if prog.is_first:
                            gp = prog.bwd(stage_params[s], x, dy)
                        else:
                            gp, dx = prog.bwd(stage_params[s], x, dy)
                            self.up[s - 1].send(dx)
                        grads[s] = _tree_add(grads[s], gp)
                if sched is not None:
                    sched.tick(s, fwd=f_valid, bwd=b_valid)
        assert all(len(q) == 0 for q in saved)
        assert all(len(e) == 0 for e in self.down + self.up)
        denom = jnp.float32(B * L)
        merged = merge_stage_grads(grads)
        merged = jax.tree.map(lambda g: g.astype(jnp.float32) / denom,
                              merged)
        return loss_sum / denom, merged

    # ---- training ------------------------------------------------------

    def init_state(self, params: dict):
        return self.optimizer.init(params)

    def train_step(self, params: dict, opt_state, inputs, targets,
                   guard=None) -> tuple:
        """(params, opt_state, loss, skipped) — guard-skip is HOST-side:
        a non-finite harvested loss leaves params/opt_state untouched
        (the no-op update the chaos drills assert), and ``guard``
        (resilience.guard.StepGuard) accounts the streak."""
        loss, grads = self.step_grads(params, inputs, targets)
        loss_f = float(np.asarray(loss))
        if self._chaos_hook is not None:
            loss_f = float(self._chaos_hook(loss_f, self._step))
        skipped = not np.isfinite(loss_f)
        if not skipped:
            mask = self.optimizer.decay_mask(params)
            params, opt_state = self.optimizer.apply(
                params, grads, opt_state, decay_mask=mask)
        else:
            self.skipped_steps += 1
        if guard is not None:
            guard.record(self._step, skipped, loss_f)
        if self.scheduler is not None:
            self.scheduler.step_done(self._step)
        self._step += 1
        return params, opt_state, loss_f, skipped

    def edge_stats(self) -> dict:
        return {
            "down": [e.stats() for e in self.down],
            "up": [e.stats() for e in self.up],
            "cross_boundaries": self.topology.cross_boundaries(),
            "skipped_steps": self.skipped_steps,
        }


def _tree_add(acc, g):
    if acc is None:
        return jax.tree.map(lambda x: x.astype(jnp.float32), g)
    return jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)


# ---------------------------------------------------------------------------
# HLO overlap controls (utils/hlo_comm verdicts; round-10 satellite).
#
# The SPMD step IS the in-slice compiled artifact of this rung: its
# per-tick ppermutes are the edge collectives, and the overlap scanner
# must find them interleavable with stage compute. The negative control
# compiles the shape MPMD must NOT have — all stage compute first, then
# one concatenated mega-edge transfer — where every FLOP is an ancestor
# of the single collective and nothing can overlap.
# ---------------------------------------------------------------------------


def spmd_pipeline_hlo(model, mesh, num_micro: int, seq_len: int,
                      batch: int) -> str:
    """Compiled HLO of the SPMD 1F1B grad step on ``mesh`` (positive
    overlap control: per-tick edge ppermutes interleave with compute)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_ddp.parallel.mesh import PIPE_AXIS
    from tpu_ddp.parallel.pipeline import (pipeline_1f1b_grads,
                                           pipeline_param_specs,
                                           stack_block_params)
    pp = mesh.shape[PIPE_AXIS]
    params = stack_block_params(model.init(jax.random.key(0)))
    specs = pipeline_param_specs(model)

    def step(p, x, y):
        def body(p, x, y):
            ls, n, g = pipeline_1f1b_grads(
                model, p, x, y, pp_size=pp, num_micro=num_micro)
            return ls[None], g
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(specs, P(), P()),
                             out_specs=(P(PIPE_AXIS), specs),
                             check_vma=False)(p, x, y)

    x = jnp.zeros((batch, seq_len), jnp.int32)
    p = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P)))
    return jax.jit(step).lower(p, x, x).compile().as_text()


def mega_edge_hlo(model, mesh, num_micro: int, seq_len: int,
                  batch: int) -> str:
    """Negative control: every microbatch's stage forward runs first,
    the activations concatenate into ONE mega ppermute, and the result
    feeds the loss — the single heavy transfer depends on ALL compute
    and feeds ALL remaining compute, so ``assert_overlap`` must fail."""
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_ddp.parallel.mesh import PIPE_AXIS
    from tpu_ddp.parallel.pipeline import (pipeline_param_specs,
                                           stack_block_params)
    pp = mesh.shape[PIPE_AXIS]
    params = stack_block_params(model.init(jax.random.key(0)))
    specs = pipeline_param_specs(model)
    del num_micro  # the mega edge is schedule-free by construction
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    pos = model._positions(seq_len)

    def body(p, x, y):
        cd = model.compute_dtype
        h = p["embed"][x].astype(cd)          # (B, L, dm)

        def layer_body(h, layer):
            h, _ = model.block_apply_aux(layer, h, pos, None)
            return h, None
        h, _ = lax.scan(layer_body, h, p["blocks"])
        # ALL microbatches' boundary activations in one transfer: the
        # anti-pattern (a GPipe-style bulk handoff) the per-tick
        # schedules exist to avoid.
        h = lax.ppermute(h.astype(jnp.float32), PIPE_AXIS, perm)
        logits = model.head_apply(
            {"ln_f": p["ln_f"], "head": p["head"]}, h.astype(cd))
        from tpu_ddp.ops.loss import softmax_cross_entropy
        nll = softmax_cross_entropy(
            logits.reshape(-1, logits.shape[-1]), y.reshape(-1))
        return jnp.sum(nll)[None]

    def step(p, x, y):
        return jax.shard_map(body, mesh=mesh, in_specs=(specs, P(), P()),
                             out_specs=P(PIPE_AXIS),
                             check_vma=False)(p, x, y)

    x = jnp.zeros((batch, seq_len), jnp.int32)
    p = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P)))
    return jax.jit(step).lower(p, x, x).compile().as_text()
