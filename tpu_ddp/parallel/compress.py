"""Compressed gradient collectives — bf16/int8 wire formats for the ladder.

Every rung of the sync ladder ships gradients at fp32; this layer wraps
any rung with a reduced wire format while keeping fp32 accumulation:

- ``none``  — no-op (the fp32 baseline).
- ``bf16``  — gradients cast to bfloat16 before the collective, mean
  accumulated in fp32 after. Stateless; 2x the wire bytes back.
- ``int8``  — blockwise int8 quantization (per-block fp32 scales over
  ``block_size``-element blocks, stochastic rounding) with an
  error-feedback residual: each device re-injects the quantization
  error it introduced into its NEXT step's gradient, so the bias of
  the lossy wire telescopes away (Seide et al.'s 1-bit-SGD trick,
  generalized to 8 bits). ~4x the wire bytes back.
- ``int8-noef`` — int8 without the residual (ablation: shows the drift
  error feedback removes; tests/test_compress.py pins it).
- ``sparse`` — LOSSLESS zero-chunk elision (EdgeCodec only): the flat
  payload is cut into ``block_size``-element chunks, a packed bitmap
  marks the nonzero ones, and only those travel at fp32. Exact (no
  error feedback to carry), and the natural wire for MoE expert
  deltas, where one optimizer step touches only the routed-to experts
  and every untouched expert row is an all-zero delta chunk
  (tpu_ddp/publish/, experiments/moe_sweep.json).

Wire scheme. A compressed all-reduce is built from dtype-PRESERVING
movement collectives instead of an arithmetic ``psum``:

    phase 1 (reduce):    all_to_all of quantized rows — each device
                         receives every peer's row of ITS 1/N chunk and
                         accumulates the mean in fp32;
    phase 2 (broadcast): the owner re-quantizes its chunk's mean and
                         all_gathers it (replicated rungs only — the
                         ZeRO/FSDP scattered path stops after phase 1,
                         exactly the folded reduce_scatter半 they need).

Two reasons this shape, both load-bearing:

1. Wire volume. At N devices an fp32 all-reduce moves 8S(N-1)/N bytes
   for S gradient elements. The two-phase scheme moves 2 * wS(N-1)/N
   (w = wire bytes/element), i.e. exactly 8/(2w): 2.0x for bf16, ~3.9x
   for int8 (+1/64 scale overhead). A naive "all_gather the quantized
   gradients" moves (N-1)wS — at w=1, N=8 that is NO reduction.
2. HLO verifiability. Arithmetic collectives are subject to backend
   float-legalization: XLA:CPU's FloatNormalization rewrites a bf16
   ``all-reduce`` to convert→f32-all-reduce→convert, silently widening
   the wire back to fp32 (measured; the numerics keep the bf16
   rounding, the bytes don't shrink). Movement collectives at INTEGER
   dtypes are untouched by that pass on every backend, so bf16 payloads
   travel bitcast as ``u16`` and int8 as ``s8`` — the compiled-HLO
   invariant (tests/test_compress.py, scripts/comm_volume.py) can then
   assert the reduced dtype is really on the wire, not constant-folded
   away (utils/hlo_comm.py scans for it).

Error-feedback algebra (int8). With per-device residual r_i and
acc_i = g_i + r_i, phase 1 introduces e1_i = acc_i - deq(q(acc_i)) on
device i and phase 2 introduces e2 = m - deq(q(m)) on the chunk's
owner, where m is the fp32 mean of the dequantized rows. The applied
gradient is mean_i(acc_i) - mean_i(e1_i) - e2, so setting

    r_i' = e1_i  +  N * e2   (the owner's chunk only)

makes mean_i(r_i') equal the full error — the residual carried into the
next step compensates exactly (owner-attributed: only the device that
quantized the mean charges itself the broadcast error, scaled by N so
the mean over devices recovers it once).

The residual pytree lives in ``TrainState.comp_state`` (engine.py):
threaded through the jitted step's carry, donated with params/opt
state, checkpointed, selected OLD on a StepGuard skip (a skipped step
must not consume residuals), and reset to zeros on restore-mismatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SPECS = ("none", "bf16", "int8", "int8-noef")

# Point-to-point-only wires (EdgeCodec / the publish delta push). The
# collective compressor cannot ship "sparse": its all_to_all phases
# need static per-device payload shapes, while the sparse wire's whole
# point is a data-dependent payload size — fine on a host-loop edge.
EDGE_SPECS = SPECS + ("sparse",)

# Replicated rungs the compressor can wrap (kind -> collective shape);
# the ZeRO/FSDP rungs use scatter_mean instead.
REPLICATED_KINDS = ("gather_scatter", "all_reduce", "fused")


def get_compressor(spec: str | None, block_size: int = 256
                   ) -> "GradCompressor":
    """Resolve a compressor spec string (None == 'none')."""
    return GradCompressor(spec or "none", block_size=block_size)


class GradCompressor:
    """Gradient wire compression for one sync rung.

    Jit-side entry points (call INSIDE the shard_map'd step):

    - :meth:`sync_replicated` — full compressed mean for the replicated
      rungs (gather_scatter / all_reduce / fused); replaces ``sync_fn``.
    - :meth:`scatter_mean` — phase-1-only compressed reduce_scatter for
      ZeRO-1/FSDP: per-leaf 1/N fp32 mean slices in the flat-padded
      layout ``parallel/zero.py`` uses (chunk = ceil(size/N), so the
      slices feed ``ZeRO1.apply_scattered``/``ZeRO3.apply`` directly).

    Host-side: :meth:`init_state` builds the carried state (int8 only —
    a replicated uint32 seed counter for stochastic rounding, plus the
    per-device error-feedback residual, global shape (dp, *leaf_shape)
    sharded over dp); :meth:`state_specs` its shard_map specs.
    """

    def __init__(self, spec: str = "none", block_size: int = 256):
        if spec not in SPECS:
            raise ValueError(
                f"unknown grad_compress spec {spec!r}; available: "
                f"{list(SPECS)}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.spec = spec
        self.block_size = int(block_size)
        self.is_int8 = spec.startswith("int8")
        self.error_feedback = spec == "int8"
        # Only int8 carries state (seed counter + residual); bf16 is a
        # pure cast and 'none' a no-op.
        self.stateful = self.is_int8
        self.wire_dtype = ("s8" if self.is_int8
                           else "u16" if spec == "bf16" else None)

    def describe(self) -> dict:
        """JSON-serializable summary (bench.py's extra.grad_compress)."""
        return {"spec": self.spec, "wire_dtype": self.wire_dtype,
                "block_size": self.block_size if self.is_int8 else None,
                "error_feedback": self.error_feedback}

    # ---- carried state (host side) -------------------------------------

    def init_state(self, params_template, dp: int, seed: int = 0,
                   abstract: bool = False):
        """Fresh comp state for a dp-way mesh, or None when stateless.

        ``params_template`` supplies CANONICAL leaf shapes (under FSDP
        the compressed path differentiates w.r.t. the gathered full
        params, so residuals are canonical-shaped there too). Residual
        leaves are host numpy — the engine device_puts them P(dp).
        ``abstract=True`` returns ShapeDtypeStructs (for spec/template
        derivation without allocating dp full param copies)."""
        if not self.stateful:
            return None
        state = {"seed": (jax.ShapeDtypeStruct((), np.uint32) if abstract
                          else np.uint32(seed))}
        if self.error_feedback:
            if abstract:
                mk = lambda t: jax.ShapeDtypeStruct(  # noqa: E731
                    (dp,) + tuple(t.shape), np.float32)
            else:
                mk = lambda t: np.zeros(  # noqa: E731
                    (dp,) + tuple(t.shape), np.float32)
            state["residual"] = jax.tree.map(mk, params_template)
        return state

    def state_specs(self, comp_state):
        """shard_map spec tree for :meth:`init_state`'s output: the seed
        counter replicated, residual leaves sharded over dp's leading
        axis (each device carries ITS OWN error)."""
        from jax.sharding import PartitionSpec as P

        from tpu_ddp.parallel.mesh import DATA_AXIS
        if comp_state is None:
            return None
        specs = {"seed": P()}
        if "residual" in comp_state:
            specs["residual"] = jax.tree.map(lambda _: P(DATA_AXIS),
                                             comp_state["residual"])
        return specs

    # ---- quantization kernel -------------------------------------------

    def _quant(self, x, key):
        """Blockwise int8 over the LAST axis (must be % block_size):
        per-block scale = max|x|/127, stochastic rounding via
        floor(x/scale + u), u ~ U[0,1) — unbiased per element."""
        b = self.block_size
        blk = x.reshape(x.shape[:-1] + (-1, b))
        amax = jnp.max(jnp.abs(blk), axis=-1)
        scale = jnp.maximum(amax / 127.0, jnp.float32(1e-30))
        u = jax.random.uniform(key, blk.shape, jnp.float32)
        q = jnp.clip(jnp.floor(blk / scale[..., None] + u), -127, 127)
        return q.astype(jnp.int8).reshape(x.shape), scale

    def _dequant(self, q, scale):
        b = self.block_size
        blk = q.astype(jnp.float32).reshape(q.shape[:-1] + (-1, b))
        return (blk * scale[..., None]).reshape(q.shape)

    # ---- bf16 wire (stateless) -----------------------------------------

    @staticmethod
    def _to_wire_bf16(x):
        """f32 -> bf16, bitcast u16 so backend float-normalization can
        never widen the collective back to f32 (module docstring)."""
        return lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)

    @staticmethod
    def _from_wire_bf16(w):
        return lax.bitcast_convert_type(w, jnp.bfloat16).astype(jnp.float32)

    # ---- layout helpers ------------------------------------------------

    def _pad_to(self, flat, total):
        return jnp.pad(flat, (0, total - flat.shape[0]))

    def _qchunk(self, chunk: int) -> int:
        """Chunk rounded up to a whole number of quant blocks (the extra
        tail is quantization-internal padding, sliced off after)."""
        b = self.block_size
        return -(-chunk // b) * b

    # ---- the two-phase compressed mean ---------------------------------

    def _bf16_two_phase(self, flat, chunk, axis_name, n):
        """(n*chunk,) f32 -> exact-dp-mean-of-bf16-payloads, re-broadcast
        at bf16. Movement collectives only; fp32 accumulation."""
        rows = self._to_wire_bf16(flat.reshape(n, chunk))
        rows = lax.all_to_all(rows, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
        m = jnp.mean(self._from_wire_bf16(rows), axis=0)      # (chunk,)
        full = lax.all_gather(self._to_wire_bf16(m), axis_name,
                              tiled=True)                     # (n*chunk,)
        return self._from_wire_bf16(full)

    def _int8_phase1(self, flat, chunk, axis_name, n, key):
        """Quantized all_to_all reduce: (n*chunk,) f32 ->
        (my chunk's fp32 mean (chunk,), my phase-1 error (n*chunk,))."""
        qchunk = self._qchunk(chunk)
        rows = flat.reshape(n, chunk)
        rows_q = jnp.pad(rows, ((0, 0), (0, qchunk - chunk)))
        q1, s1 = self._quant(rows_q, key)
        deq_own = self._dequant(q1, s1)[:, :chunk]
        err = (rows - deq_own).reshape(-1)
        q1t = lax.all_to_all(q1, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)
        s1t = lax.all_to_all(s1, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)
        m = jnp.mean(self._dequant(q1t, s1t)[:, :chunk], axis=0)
        return m, err

    def _int8_two_phase(self, flat, chunk, axis_name, n, key):
        """Full compressed all-reduce: phase-1 reduce + re-quantized
        all_gather broadcast. Returns (mean (n*chunk,), err (n*chunk,))
        with the phase-2 error owner-attributed at N x into this
        device's chunk (module docstring algebra)."""
        k1, k2 = jax.random.split(key)
        m, err = self._int8_phase1(flat, chunk, axis_name, n, k1)
        qchunk = self._qchunk(chunk)
        q2, s2 = self._quant(self._pad_to(m, qchunk), k2)
        full_q = lax.all_gather(q2, axis_name, tiled=False)   # (n, qchunk)
        full_s = lax.all_gather(s2, axis_name, tiled=False)
        out = self._dequant(full_q, full_s)[:, :chunk].reshape(-1)
        e2 = m - self._dequant(q2, s2)[:chunk]
        idx = lax.axis_index(axis_name)
        own = lax.dynamic_slice(err, (idx * chunk,), (chunk,))
        err = lax.dynamic_update_slice(err, own + n * e2, (idx * chunk,))
        return out, err

    def _int8_gather_all(self, flat, axis_name, n, key):
        """gather_scatter wire shape: every device quantizes its FULL
        payload and all_gathers it; each replica dequantizes and means
        locally (identical values everywhere, so the reference's
        root-selects-the-mean step is a no-op and elided). Returns
        (mean (L,), err (L,))."""
        total = flat.shape[0]
        qtotal = self._qchunk(total)
        q, s = self._quant(self._pad_to(flat, qtotal), key)
        err = flat - self._dequant(q, s)[:total]
        qg = lax.all_gather(q, axis_name, tiled=False)        # (n, qtotal)
        sg = lax.all_gather(s, axis_name, tiled=False)
        m = jnp.mean(self._dequant(qg, sg)[:, :total], axis=0)
        return m, err

    # ---- per-step PRNG -------------------------------------------------

    def _device_key(self, comp, axis_name):
        """Per-(step, device) base key; per-leaf keys fold the leaf
        index in. Each device quantizes only its OWN payloads, so keys
        need not agree across devices — determinism of the applied
        gradient comes from the all_gathered phase-2 bytes."""
        base = jax.random.key(comp["seed"])
        return jax.random.fold_in(base, lax.axis_index(axis_name))

    @staticmethod
    def _bump_seed(comp):
        return comp["seed"] + jnp.uint32(1)

    # ---- residual plumbing ---------------------------------------------

    @staticmethod
    def _res_leaf(comp, i, g):
        """Residual for leaf i as a g-shaped array (the shard_map block
        of the (dp, *shape) leaf is (1, *shape))."""
        return jax.tree.leaves(comp["residual"])[i].reshape(g.shape)

    # ---- public jit-side API -------------------------------------------

    def sync_replicated(self, kind, grads, comp, axis_name, n):
        """Compressed replacement for the replicated rungs' ``sync_fn``:
        (grads, comp) -> (synced fp32 grads, new comp). Call inside the
        shard_map'd step; ``kind`` picks the rung's collective shape
        (one pair per leaf for all_reduce, ONE pair for the whole
        concatenated tree for fused, a full-payload all_gather for
        gather_scatter)."""
        if kind not in REPLICATED_KINDS:
            raise ValueError(f"sync_replicated got kind {kind!r}; "
                             f"expected one of {REPLICATED_KINDS}")
        if self.spec == "none":
            raise ValueError("sync_replicated on a 'none' compressor; "
                             "use the rung's sync_fn")
        leaves, treedef = jax.tree.flatten(grads)
        if self.spec == "bf16":
            out = [self._bf16_leaf(kind, g, axis_name, n) for g in leaves]
            return treedef.unflatten(out), None
        return self._int8_replicated(kind, leaves, treedef, comp,
                                     axis_name, n)

    def _bf16_leaf(self, kind, g, axis_name, n):
        size = g.size
        flat = g.astype(jnp.float32).reshape(-1)
        if kind == "gather_scatter":
            stacked = lax.all_gather(self._to_wire_bf16(flat), axis_name,
                                     tiled=False)             # (n, size)
            return jnp.mean(self._from_wire_bf16(stacked),
                            axis=0).reshape(g.shape)
        chunk = -(-size // n)
        out = self._bf16_two_phase(self._pad_to(flat, n * chunk), chunk,
                                   axis_name, n)
        return out[:size].reshape(g.shape)

    def _int8_replicated(self, kind, leaves, treedef, comp, axis_name, n):
        key = self._device_key(comp, axis_name)
        new_comp = dict(comp)
        new_comp["seed"] = self._bump_seed(comp)

        def acc_for(i, g):
            flat = g.astype(jnp.float32).reshape(-1)
            if self.error_feedback:
                flat = flat + self._res_leaf(comp, i, g).reshape(-1)
            return flat

        if kind == "fused":
            # ONE collective pair for the whole tree: concatenate the
            # accumulated leaves, run the two-phase mean once, split.
            sizes = [g.size for g in leaves]
            flat = jnp.concatenate([acc_for(i, g)
                                    for i, g in enumerate(leaves)])
            total = int(sum(sizes))
            chunk = -(-total // n)
            m, err = self._int8_two_phase(
                self._pad_to(flat, n * chunk), chunk, axis_name, n,
                jax.random.fold_in(key, 0))
            outs, errs, off = [], [], 0
            for g, size in zip(leaves, sizes):
                outs.append(m[off:off + size].reshape(g.shape))
                errs.append(err[off:off + size])
                off += size
        else:
            outs, errs = [], []
            for i, g in enumerate(leaves):
                size = g.size
                flat = acc_for(i, g)
                leaf_key = jax.random.fold_in(key, i)
                if kind == "gather_scatter":
                    m, err = self._int8_gather_all(flat, axis_name, n,
                                                   leaf_key)
                else:  # all_reduce: one pair per leaf
                    chunk = -(-size // n)
                    m, err = self._int8_two_phase(
                        self._pad_to(flat, n * chunk), chunk, axis_name,
                        n, leaf_key)
                outs.append(m[:size].reshape(g.shape))
                errs.append(err[:size])
        if self.error_feedback:
            res_leaves = jax.tree.leaves(comp["residual"])
            new_comp["residual"] = jax.tree.unflatten(
                jax.tree.structure(comp["residual"]),
                [e[:r.size].reshape(r.shape)
                 for e, r in zip(errs, res_leaves)])
        return treedef.unflatten(outs), new_comp

    def scatter_mean(self, grads, comp, axis_name, n):
        """Compressed reduce_scatter for the ZeRO-1/FSDP rungs: (grads,
        comp) -> (per-leaf (chunk,) fp32 MEAN slices, new comp) with
        chunk = ceil(size/N) — the exact flat-padded layout
        ``ZeRO1.apply_scattered`` and ``ZeRO3.apply`` consume. Phase 1
        only: the result stays scattered (the rung's parameter
        all_gather is its own second half and stays fp32 — parameters,
        not gradients, are out of this layer's scope)."""
        leaves, treedef = jax.tree.flatten(grads)
        if self.spec == "bf16":
            def leaf(g):
                size = g.size
                chunk = -(-size // n)
                flat = self._pad_to(g.astype(jnp.float32).reshape(-1),
                                    n * chunk)
                rows = lax.all_to_all(
                    self._to_wire_bf16(flat.reshape(n, chunk)), axis_name,
                    split_axis=0, concat_axis=0, tiled=True)
                return jnp.mean(self._from_wire_bf16(rows), axis=0)
            return treedef.unflatten([leaf(g) for g in leaves]), None
        key = self._device_key(comp, axis_name)
        new_comp = dict(comp)
        new_comp["seed"] = self._bump_seed(comp)
        outs, errs = [], []
        for i, g in enumerate(leaves):
            size = g.size
            chunk = -(-size // n)
            flat = g.astype(jnp.float32).reshape(-1)
            if self.error_feedback:
                flat = flat + self._res_leaf(comp, i, g).reshape(-1)
            m, err = self._int8_phase1(
                self._pad_to(flat, n * chunk), chunk, axis_name, n,
                jax.random.fold_in(key, i))
            outs.append(m)
            errs.append(err[:size])
        if self.error_feedback:
            res_leaves = jax.tree.leaves(comp["residual"])
            new_comp["residual"] = jax.tree.unflatten(
                jax.tree.structure(comp["residual"]),
                [e.reshape(r.shape) for e, r in zip(errs, res_leaves)])
        return treedef.unflatten(outs), new_comp


# ---------------------------------------------------------------------------
# Point-to-point edge codec (round 10, MPMD pipeline).
#
# The collectives above compress an ALL-REDUCE; an MPMD pipeline edge is
# a point-to-point handoff of one activation (down) or cotangent (up)
# tensor per tick. Same wire formats, different shape: no phases, no
# all_to_all — just encode on the sending stage, ship the reduced
# payload over DCN, decode on the receiver. Error feedback carries PER
# EDGE on the sender: each tick's quantization error is added to the
# next payload on the same edge, so the bias telescopes along the
# training trajectory exactly as it does for gradients (the edge sees
# the same microbatch slot every M ticks, and the loss is what
# accumulates the bias — tests/test_mpmd.py pins the trajectory).
# ---------------------------------------------------------------------------


class EdgeCodec:
    """Wire codec for ONE directed MPMD edge.

    Stateful on the sender side (int8 stochastic-rounding seed counter
    + optional error-feedback residual); the receiver only needs
    :meth:`decode`, which is stateless. The MPMD scheduler is a host
    loop, so host-held mutable state is the natural form here — unlike
    the jit-carried ``comp_state`` of the collective compressor.

    ``encode`` returns ``(wire, nbytes)`` where ``wire`` is a dict of
    arrays that actually travel and ``nbytes`` counts their payload
    bytes (the honest numerator for the compression-ratio acceptance
    numbers; fp32 would be ``4 * x.size``).
    """

    def __init__(self, spec: str = "none", block_size: int = 256,
                 seed: int = 0):
        if spec not in EDGE_SPECS:
            raise ValueError(
                f"unknown edge codec spec {spec!r}; available: "
                f"{list(EDGE_SPECS)}")
        self.spec = spec
        self.is_int8 = spec.startswith("int8")
        self.error_feedback = spec == "int8"
        # Kernel host: borrows _quant/_dequant (and block_size
        # validation) from the collective compressor.
        self._k = GradCompressor("int8" if self.is_int8 else "none",
                                 block_size=block_size)
        self.block_size = self._k.block_size
        self._seed = np.uint32(seed)
        self._residual = None   # lazily sized to the edge payload
        self.bytes_sent = 0     # cumulative wire bytes (stats surface)
        self.bytes_dense = 0    # what fp32 would have cost

    def describe(self) -> dict:
        return {"spec": self.spec,
                "block_size": self.block_size if self.is_int8 else None,
                "error_feedback": self.error_feedback}

    @property
    def ratio(self) -> float:
        """Achieved dense/wire byte ratio so far (1.0 before traffic)."""
        return (self.bytes_dense / self.bytes_sent
                if self.bytes_sent else 1.0)

    def reset(self) -> None:
        """Drop carried state (elastic restart: a new edge peer must
        not inherit a residual accumulated against the old one)."""
        self._residual = None
        self.bytes_sent = 0
        self.bytes_dense = 0

    # ---- sender --------------------------------------------------------

    def encode(self, x) -> tuple[dict, int]:
        x = jnp.asarray(x, jnp.float32)
        self.bytes_dense += 4 * x.size
        if self.spec == "none":
            wire = {"kind": "none", "payload": x}
            nbytes = 4 * x.size
        elif self.spec == "bf16":
            wire = {"kind": "bf16",
                    "payload": GradCompressor._to_wire_bf16(x)}
            nbytes = 2 * x.size
        elif self.spec == "sparse":
            wire, nbytes = self._encode_sparse(x)
        else:
            wire, nbytes = self._encode_int8(x)
        self.bytes_sent += nbytes
        return wire, nbytes

    def _encode_sparse(self, x) -> tuple[dict, int]:
        """Lossless zero-chunk elision: chunk the flat fp32 payload at
        ``block_size``, packbits which chunks hold any nonzero, ship
        only those. A host-side codec (the sparsity pattern sizes the
        payload — exactly what a compiled collective cannot do), which
        is where EdgeCodec already lives. Worst case (nothing zero)
        costs the dense bytes + the ~size/8B bitmap; best case (an MoE
        delta touching few experts) drops whole untouched expert rows.
        """
        b = self.block_size
        flat = np.asarray(x, np.float32).reshape(-1)
        n = max(1, -(-flat.size // b))
        padded = np.zeros((n * b,), np.float32)
        padded[:flat.size] = flat
        rows = padded.reshape(n, b)
        nz = np.any(rows != 0.0, axis=1)                    # (n,) bool
        wire = {"kind": "sparse", "payload": jnp.asarray(rows[nz]),
                "mask": np.packbits(nz), "chunks": n, "chunk": b,
                "shape": tuple(np.shape(x))}
        return wire, 4 * int(nz.sum()) * b + int(np.packbits(nz).size)

    def _encode_int8(self, x) -> tuple[dict, int]:
        flat = x.reshape(-1)
        if self.error_feedback:
            if (self._residual is None
                    or self._residual.shape != flat.shape):
                self._residual = jnp.zeros_like(flat)
            flat = flat + self._residual
        qtotal = self._k._qchunk(flat.shape[0])
        key = jax.random.key(self._seed)
        self._seed = np.uint32(self._seed + np.uint32(1))
        q, scale = self._k._quant(self._k._pad_to(flat, qtotal), key)
        if self.error_feedback:
            deq = self._k._dequant(q, scale)[:flat.shape[0]]
            self._residual = flat - deq
        wire = {"kind": "int8", "q": q, "scale": scale,
                "shape": tuple(x.shape)}
        return wire, q.size + 4 * scale.size

    # ---- receiver (stateless) ------------------------------------------

    @staticmethod
    def decode(wire: dict):
        kind = wire["kind"]
        if kind == "none":
            return wire["payload"]
        if kind == "bf16":
            return GradCompressor._from_wire_bf16(wire["payload"])
        if kind == "int8":
            shape = wire["shape"]
            size = int(np.prod(shape)) if shape else 1
            k = GradCompressor("int8",
                               block_size=wire["q"].size
                               // wire["scale"].size)
            flat = k._dequant(wire["q"], wire["scale"])[:size]
            return flat.reshape(shape)
        if kind == "sparse":
            n, b = int(wire["chunks"]), int(wire["chunk"])
            nz = np.unpackbits(np.asarray(wire["mask"]),
                               count=n).astype(bool)
            rows = np.zeros((n, b), np.float32)
            if nz.any():
                rows[nz] = np.asarray(wire["payload"],
                                      np.float32).reshape(-1, b)
            shape = wire["shape"]
            size = int(np.prod(shape)) if shape else 1
            return jnp.asarray(rows.reshape(-1)[:size].reshape(shape))
        raise ValueError(f"unknown edge wire kind {kind!r}")


# ---------------------------------------------------------------------------
# Cold-page codec (tpu_ddp/serve/kv_pool.py tiered KV, DESIGN.md §27).
# The SAME per-block int8 scheme as GradCompressor._quant — scale =
# max|x|/127 clamped away from zero — but with DETERMINISTIC
# round-to-nearest instead of stochastic rounding: a KV page demoted
# and promoted twice must dequantize identically both times (replay /
# migration parity is position-keyed, never RNG-keyed), and there is
# no error-feedback loop to absorb rounding bias here. The scale is
# per (layer, page, token-row) — one row's outlier cannot flatten its
# neighbours' resolution — matching the disagg KV wire's granularity
# choice (fleet/disagg.py zero-masks garbage tails for the same
# reason).
# ---------------------------------------------------------------------------


def page_quantize(x, cold_dtype):
    """Quantize KV pages ``x`` (..., bs, KV*hd) for cold storage.

    Returns ``(q, scale)`` with scale shaped like ``x`` minus the
    trailing row axis (one scale per token row, all heads). ``cold_dtype`` jnp.int8 -> per-row symmetric
    int8; jnp.bfloat16 -> a plain downcast with unit scales (lossless
    when the hot dtype is already bf16 — the parity-testing tier)."""
    if cold_dtype == jnp.bfloat16:
        return (x.astype(jnp.bfloat16),
                jnp.ones(x.shape[:-1], jnp.float32))
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax / 127.0, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def page_dequantize(q, scale, out_dtype):
    """Inverse of :func:`page_quantize`: (..., bs, KV*hd) pages back
    in ``out_dtype`` (the pool's hot dtype)."""
    return (q.astype(jnp.float32)
            * scale[..., None]).astype(out_dtype)
