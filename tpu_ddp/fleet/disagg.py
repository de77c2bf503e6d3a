"""Prefill/decode disaggregation: two engine roles, one explicit edge.

Why split the roles at all: in the round-12 single engine, a prefill
chunk and the whole-bank decode step share one host loop, so a burst
of long prompts steals engine steps from live decodes (TTFT for the
burst trades directly against TPOT for everyone else). The fleet
answer — the DistServe/Splitwise argument — is to pin prefill to its
own worker whose pool only ever holds prompts, and stream each
finished prompt's KV blocks to the decode worker over an explicit
edge. Decode steps then never wait on prefill compute; prefill
capacity scales independently of decode capacity.

The edge is the MPMD round's machinery pointed at serving: the payload
rides :class:`tpu_ddp.parallel.compress.EdgeCodec` wire formats
("none" / "bf16" / "int8" — the ``kv_wire`` knob), so a DCN-crossing
role split pays 2–4x fewer bytes per prompt. int8 rides the
error-feedback-free variant: each transfer is an independent one-shot
payload (a different request's KV), so there is no trajectory along
which a residual could telescope. Garbage tail positions of the last
prompt block are zero-masked before encoding — stale values would
pollute the per-block int8 scales.

Adoption is free-list surgery, not a copy: the decode pool allocates
block ids, the payload lands in them with ONE scatter fused into the
front of the decode step (``_build_adopt_decode_step``), and the
request's slot starts directly in the decode phase. The fused program
applies the adoption scatter BEFORE the bank's own writes/gathers —
the adopted ids are in no live table this step, so the decode math is
untouched, and the scatter's dependence cones leave every layer's
QKV/MLP projections free: ``utils/hlo_comm.update_overlap_report``
checks exactly that, i.e. a latency-hiding scheduler is ALLOWED to
run the transfer landing behind decode compute.

Sampling stays stateless-keyed by (seed, position) on both sides, so
any role split reproduces the single engine's tokens bitwise with
``kv_wire="none"`` — the parity acceptance criterion. Lossy wires
round the shipped KV and are gated as semantic, like cache dtype.

Degraded mode (docs/DESIGN.md §23): a transfer lost on the edge, or
the prefill worker dying outright, must not wedge the pipeline. The
decode worker owns a one-slot fallback scheduler (``dsched``) over its
OWN pool and re-runs the lost request's prefill locally, chunked, with
the same jitted prefill program at the decode pool's shapes — single-
engine semantics, already bitwise-pinned, so degraded output equals
healthy output token for token (recomputed KV is recomputed, not
migrated). Prefill-worker death flips ``prefill_degraded``: every
pending prompt (mid-prefill, queued, and in-flight edge transfers) is
reaped and replayed locally, and later submits skip the dead role
entirely. A warning marks each degradation; nothing is silently lost.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
import warnings
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from tpu_ddp.models.decode import check_decodable, check_state_servable
from tpu_ddp.parallel.compress import EdgeCodec
from tpu_ddp.serve.engine import (
    Request,
    _build_prefill_step,
    decode_bank,
)
from tpu_ddp.serve.kv_pool import PagedKVPool, pin_committed
from tpu_ddp.serve.scheduler import (
    Scheduler,
    parse_tenant_classes,
    tenant_of,
)
from tpu_ddp.utils.metrics import MetricsLogger
from tpu_ddp.utils.profiling import SERVE_ADOPT_DECODE, program


@functools.lru_cache(maxsize=32)
def _build_adopt_decode_step(model, block_size: int,
                             blocks_per_seq: int):
    """The fused transfer-landing + whole-bank decode program.
    ``adopt_ids`` (nb,) are freshly allocated (table-less) block ids;
    ``adopt_k``/``adopt_v`` (L, nb, bs, KV*hd) is the decoded wire
    payload. The scatter runs FIRST so it depends on nothing the
    decode computes and nothing heavy depends on it — the dataflow
    freedom ``update_overlap_report`` verifies."""

    @program(SERVE_ADOPT_DECODE)
    def step(params, pool_k, pool_v, adopt_ids, adopt_k, adopt_v,
             tables, lengths, last_tokens, temps, seeds):
        pool_k = pool_k.at[:, adopt_ids].set(
            adopt_k.astype(pool_k.dtype))
        pool_v = pool_v.at[:, adopt_ids].set(
            adopt_v.astype(pool_v.dtype))
        return decode_bank(model, block_size, params, pool_k, pool_v,
                           tables, lengths, last_tokens, temps, seeds)

    return jax.jit(step, donate_argnums=(1, 2))


@dataclasses.dataclass
class KVTransfer:
    """One finished prefill in flight on the edge: encoded KV blocks
    plus the last-token state the decode role resumes from."""

    request: Request
    wire_k: dict
    wire_v: dict
    n_blocks: int
    length: int          # prompt tokens (valid cache positions)
    pending_token: int   # first sampled token, already emitted
    nbytes: int          # wire payload bytes (both tensors)


class KVEdge:
    """The explicit prefill→decode edge: a FIFO of encoded transfers
    with one :class:`EdgeCodec` providing the wire format and the
    honest byte accounting (``bytes_sent`` / ``ratio``)."""

    def __init__(self, wire: str = "none"):
        if wire not in ("none", "bf16", "int8"):
            raise ValueError(f"kv_wire={wire!r}: expected "
                             "none|bf16|int8")
        self.wire = wire
        # int8 rides the EF-free variant: transfers are independent
        # one-shot payloads, not a trajectory a residual could follow.
        self.codec = EdgeCodec("int8-noef" if wire == "int8" else wire)
        self.queue: deque = deque()
        self.sent = 0
        self.delivered = 0
        self.dropped = 0

    def send(self, transfer: KVTransfer) -> None:
        self.queue.append(transfer)
        self.sent += 1

    def pop(self) -> KVTransfer:
        self.delivered += 1
        return self.queue.popleft()

    def drop(self, request: Request) -> bool:
        """Cancel support: remove a pending transfer for ``request``.
        Its blocks live only in the payload (the prefill side already
        freed its pool copies), so dropping the transfer IS the
        cleanup."""
        for t in self.queue:
            if t.request is request:
                self.queue.remove(t)
                self.dropped += 1
                return True
        return False

    def stats(self) -> dict:
        return {"wire": self.wire, "sent": self.sent,
                "delivered": self.delivered, "dropped": self.dropped,
                "pending": len(self.queue),
                "bytes_sent": self.codec.bytes_sent,
                "bytes_dense": self.codec.bytes_dense,
                "ratio": self.codec.ratio}


class DisaggEngine:
    """Prefill-role + decode-role pair behind the single-engine
    surface (``submit`` / ``cancel`` / ``step`` / ``run``), so
    loadgen, the router, and the sweep drive it interchangeably with
    :class:`ServeEngine`.

    One ``step()`` advances both roles once: admit + one prefill
    chunk on the prefill worker (shipping on completion), land at
    most one edge transfer on the decode worker (fused into the
    decode step when a live batch exists), one whole-bank decode
    step. Equal-simulated-hardware comparisons give the two pools a
    combined budget matching the single engine's.
    """

    def __init__(self, model, params, *,
                 num_slots: int | None = None,
                 block_size: int | None = None,
                 prefill_chunk: int | None = None,
                 num_blocks: int | None = None,
                 prefill_blocks: int | None = None,
                 cache_dtype: str | None = None,
                 kv_wire: str | None = None,
                 prefix_cache: bool | None = None,
                 queue_limit: int | None = None,
                 shed_ms: float | None = None,
                 tenant_classes: str | None = None,
                 decode_quant: str | None = None,
                 metrics: MetricsLogger | None = None,
                 config=None):
        check_decodable(model)
        check_state_servable(model, disagg=True)
        if config is None:
            from tpu_ddp.utils.config import TrainConfig
            config = TrainConfig()
        self.model = model
        self.params = pin_committed(jax.tree.map(jnp.asarray, params))
        self.num_slots = int(num_slots if num_slots is not None
                             else config.serve_slots)
        self.block_size = int(block_size if block_size is not None
                              else config.serve_block_size)
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else config.serve_prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.blocks_per_seq = math.ceil(model.max_seq_len
                                        / self.block_size)
        cache_dtype = (cache_dtype if cache_dtype is not None
                       else config.serve_cache_dtype)
        if num_blocks is None:
            num_blocks = self.num_slots * self.blocks_per_seq + 1
        if prefill_blocks is None:
            # Room for two worst-case prompts (one prefilling, one
            # admitted behind it) plus prefix-cache residency.
            prefill_blocks = 2 * self.blocks_per_seq + 1
        # Decode role: the round-12 pool + scheduler, decode-only in
        # practice (every slot is placed post-prefill).
        self.pool = PagedKVPool(model, num_blocks, self.block_size,
                                cache_dtype)
        self.sched = Scheduler(self.pool, self.num_slots, "continuous")
        # Prefill role: prompt-only reservations; finished KV ships
        # over the edge, so the prefix index (when on) lives HERE —
        # cached blocks must be in the pool the prefill step gathers.
        self.prefill_pool = PagedKVPool(model, prefill_blocks,
                                        self.block_size, cache_dtype)
        self.prefix = None
        prefix_cache = (bool(prefix_cache) if prefix_cache is not None
                        else config.prefix_cache)
        if prefix_cache:
            from tpu_ddp.fleet.prefix import PrefixIndex
            self.prefix = PrefixIndex(self.prefill_pool)
        # Tenant classes (§25) apply at the ADMISSION scheduler — the
        # prefill role's queue is where disagg requests wait. Degraded
        # mode trades WFQ for liveness (the fallback queue is FIFO):
        # with the prefill worker dead, draining anything beats
        # draining fairly.
        tc = (tenant_classes if tenant_classes is not None
              else config.tenant_classes)
        self.tenants = parse_tenant_classes(tc) or None
        self.psched = Scheduler(self.prefill_pool, 1, "continuous",
                                prefix=self.prefix, role="prefill",
                                tenants=self.tenants)
        # Degraded-mode fallback: a one-slot scheduler over the DECODE
        # pool that re-prefills requests whose edge transfer was lost
        # or whose prefill worker died. It shares the decode pool, so
        # the two schedulers are reservation peers — admitted-always-
        # finish holds across both.
        self.dsched = Scheduler(self.pool, 1, "continuous")
        self.sched.peers = [self.dsched]
        self.dsched.peers = [self.sched]
        self.prefill_degraded = False
        self.edge = KVEdge(kv_wire if kv_wire is not None
                           else config.kv_wire)
        # Weight-only int8 decode compute (§26, TPU_DDP_DECODE_QUANT)
        # for BOTH roles: one quantized tree feeds prefill, degraded
        # prefill, decode and adopt+decode, so the shipped KV and the
        # decode queries come from the same arithmetic. (Speculation
        # is NOT supported here — the decode tier runs the fused
        # adopt+decode program only; tune/space.py marks spec_k>0
        # with fleet_roles='disagg' infeasible.)
        self.decode_quant = str(
            decode_quant if decode_quant is not None
            else getattr(config, "decode_quant", "none"))
        if self.decode_quant not in ("none", "int8"):
            raise ValueError(
                f"decode_quant={self.decode_quant!r}: expected 'none'"
                " or 'int8' (TPU_DDP_DECODE_QUANT)")
        self._refresh_quant()
        self.metrics = metrics if metrics is not None \
            else MetricsLogger(None)
        self._prefill = _build_prefill_step(model, self.block_size,
                                            self.blocks_per_seq)
        self._adopt_decode = _build_adopt_decode_step(
            model, self.block_size, self.blocks_per_seq)
        self._rid = itertools.count()
        self.queue_limit = int(queue_limit if queue_limit is not None
                               else config.serve_queue_limit)
        self.shed_ms = float(shed_ms if shed_ms is not None
                             else config.serve_shed_ms)
        if self.queue_limit < 0:
            raise ValueError("queue_limit must be >= 0")
        if self.shed_ms < 0:
            raise ValueError("shed_ms must be >= 0")
        self._step_n = 0
        # Weight streaming (tpu_ddp/publish/): both roles serve ONE
        # ``self.params`` tree, passed per call to every jitted
        # program (prefill, degraded prefill, decode, adopt+decode) —
        # a subscriber flip swaps all of them at once, between steps.
        self.param_version = 0
        self.subscriber = None
        self.chaos = None
        from tpu_ddp.fleet.resilience import (
            ServeFaultInjector, serve_chaos_active)
        if serve_chaos_active():
            self.chaos = ServeFaultInjector.from_env()

    # ---- request lifecycle ---------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, seed: int = 0,
               eos_id: int | None = None,
               on_token: Callable[[int], None] | None = None,
               tenant: str = "default") -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold >= 1 token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt.size + max_new_tokens
        if total > self.model.max_seq_len:
            raise ValueError(f"prompt + generation = {total} exceeds "
                             f"max_seq_len={self.model.max_seq_len}")
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        if not tenant:
            raise ValueError("tenant must be a non-empty string")
        req = Request(rid=next(self._rid), prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), seed=int(seed),
                      eos_id=eos_id, on_token=on_token,
                      tenant=str(tenant),
                      submitted_at=time.perf_counter())
        # Decode-side feasibility must hold too, or the transfer could
        # never be adopted and would head-block the edge forever.
        dneed = self.sched.worst_case_blocks(req)
        if dneed > self.pool.total_usable:
            raise ValueError(
                f"request needs up to {dneed} decode KV blocks but "
                f"the decode pool holds only {self.pool.total_usable}")
        self.metrics.inc("serve_submitted")
        qlen = len(self.dsched.queue) if self.prefill_degraded \
            else len(self.psched.queue)
        if self.queue_limit and qlen >= self.queue_limit:
            self._shed(req)
            return req
        if self.prefill_degraded:
            self.dsched.enqueue(req)  # the prefill role is gone
        else:
            self.psched.enqueue(req)
        return req

    def _shed(self, req: Request) -> None:
        req.shed = True
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.inc("serve_shed")

    def _shed_expired(self) -> None:
        """Deadline shedding over both admission queues. Only
        requests that have not produced a token are sheddable — a
        degraded-queue request replaying a lost transfer already
        streamed its first token and must finish."""
        if not self.shed_ms:
            return
        now = time.perf_counter()
        for q in (self.psched.queue, self.dsched.queue):
            expired = [r for r in q if not r.tokens
                       and (now - r.submitted_at) * 1e3 > self.shed_ms]
            for r in expired:
                q.remove(r)
                self._shed(r)

    def cancel(self, req: Request) -> bool:
        """Drop a request anywhere in the pipeline: queued, mid-
        prefill (frees the prefill pool's reserved blocks), pending on
        the edge (drops the transfer), or decoding."""
        if req.done:
            return False
        if self.edge.drop(req):
            pass
        elif req in self.psched.queue:
            self.psched.queue.remove(req)
        elif req in self.dsched.queue:
            self.dsched.queue.remove(req)
        else:
            for sched in (self.psched, self.dsched, self.sched):
                hit = False
                for i, s in enumerate(sched.slots):
                    if s is not None and s.request is req:
                        sched.retire(i)
                        hit = True
                        break
                if hit:
                    break
            else:
                return False
        req.cancelled = True
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.inc("serve_cancelled")
        return True

    # ---- the iteration -------------------------------------------------

    def step(self) -> bool:
        """One fleet iteration: each role advances once. Degraded
        requests (lost transfer / dead prefill worker) re-prefill on
        the decode worker, one chunk per step, yielding to healthy
        prefill traffic when both exist."""
        self._step_n += 1
        if self.chaos is not None:
            # May raise ReplicaCrashError — before any state mutation.
            self.chaos.replica_step(self._step_n)
        if self.subscriber is not None:
            # Weight streaming: stage/flip between steps (see
            # ServeEngine.step) — prefill and decode roles flip
            # together, so a request never prefills on one version
            # and starts decoding on another within one step.
            self.subscriber.on_engine_step()
        self._shed_expired()
        admitted = list(self.psched.admit())
        self._promote_degraded()
        admitted += self.dsched.admit()
        did = False

        pi = self.psched.prefill_slot()
        di = self.dsched.prefill_slot()
        if pi is not None:
            did = True
            try:
                self._run_prefill_chunk(pi)
            except Exception as e:  # noqa: BLE001 — degrade, don't wedge
                self._fail_prefill(e)
        elif di is not None:
            did = True
            self._run_degraded_chunk(di)

        transfer = self._pop_adoptable()
        dslots = self.sched.decode_slots()
        if transfer is not None:
            did = True
            self._land(transfer, dslots)
        elif dslots:
            did = True
            self._run_decode_step(dslots)

        self.metrics.observe("serve_queue_depth",
                             len(self.psched.queue)
                             + len(self.dsched.queue))
        self.metrics.observe("serve_slot_occupancy",
                             self.sched.live / self.num_slots)
        return did or bool(admitted) or self.dsched.live > 0

    def run(self, max_steps: int | None = None) -> int:
        n = 0
        while max_steps is None or n < max_steps:
            if not self.step():
                break
            n += 1
        return n

    def swap_params(self, params, version: int) -> None:
        """Atomic weight flip for BOTH roles (see
        ServeEngine.swap_params): one tree feeds prefill, degraded
        prefill, decode and adopt+decode, so a single swap keeps every
        program on the same version from the next step on."""
        self.params = params
        self.param_version = int(version)
        self._refresh_quant()

    def _refresh_quant(self) -> None:
        """(Re)derive the serving parameter tree from the fp master
        ``self.params`` — at construction and after every
        :meth:`swap_params` flip (the subscriber re-quantizes on
        hot-swap without knowing the knob exists; see
        ServeEngine._refresh_quant)."""
        if self.decode_quant == "int8":
            from tpu_ddp.ops.quant import quantize_params
            self._decode_params = pin_committed(
                quantize_params(self.model, self.params))
        else:
            self._decode_params = self.params

    def stats(self) -> dict:
        """Pipeline introspection for dashboards and the sweep:
        the edge ledger, the quantization knob, and the degraded
        flag. ``speculative`` is always None — the decode tier runs
        the fused adopt+decode program only (speculation is a
        single-engine/router feature; tune/space.py marks the combo
        infeasible)."""
        return {"edge": self.edge.stats(),
                "decode_quant": self.decode_quant,
                "prefill_degraded": self.prefill_degraded,
                "speculative": None}

    # ---- router hooks --------------------------------------------------

    def outstanding(self) -> int:
        w = 0
        for q in (self.psched.queue, self.dsched.queue):
            for r in q:
                w += len(r.prompt) + r.max_new_tokens - len(r.tokens)
        for t in self.edge.queue:
            w += t.request.max_new_tokens - len(t.request.tokens)
        for sched in (self.psched, self.dsched, self.sched):
            for s in sched.slots:
                if s is not None:
                    w += (len(s.request.prompt) - s.prefill_done) \
                        + (s.request.max_new_tokens - s.generated)
        return w

    def prefix_cached_len(self, prompt, tenant: str = "default") -> int:
        if self.prefix is None:
            return 0
        return self.prefix.cached_len(
            np.asarray(prompt, np.int32).reshape(-1), ns=tenant)

    def outstanding_by_tenant(self) -> dict[str, int]:
        """``outstanding()`` by tenant (see ServeEngine) — computed
        live over queues, edge and slots, so cancels leave no ghost
        load in the autoscaler's backlog signal."""
        out: dict[str, int] = {}

        def add(t, w):
            out[t] = out.get(t, 0) + w

        for q in (self.psched.queue, self.dsched.queue):
            for r in q:
                add(tenant_of(r),
                    len(r.prompt) + r.max_new_tokens - len(r.tokens))
        for t in self.edge.queue:
            add(tenant_of(t.request),
                t.request.max_new_tokens - len(t.request.tokens))
        for sched in (self.psched, self.dsched, self.sched):
            for s in sched.slots:
                if s is not None:
                    add(tenant_of(s.request),
                        (len(s.request.prompt) - s.prefill_done)
                        + (s.request.max_new_tokens - s.generated))
        return out

    # ---- prefill role --------------------------------------------------

    def _table_for(self, slot) -> np.ndarray:
        t = np.zeros(self.blocks_per_seq, np.int32)
        t[:len(slot.blocks)] = slot.blocks
        return t

    def _run_prefill_chunk(self, pi: int) -> None:
        s = self.psched.slots[pi]
        req = s.request
        start, C = s.prefill_done, self.prefill_chunk
        chunk = np.zeros((1, C), np.int32)
        piece = req.prompt[start:start + C]
        chunk[0, :piece.size] = piece
        k, v, tok, lp = self._prefill(
            self._decode_params, self.prefill_pool.k,
            self.prefill_pool.v,
            jnp.asarray(self._table_for(s)), jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(req.prompt.size),
            jnp.float32(req.temperature), jnp.int32(req.seed))
        self.prefill_pool.commit(k, v)
        s.prefill_done = min(start + C, int(req.prompt.size))
        s.length = s.prefill_done
        if s.prefill_done >= req.prompt.size:
            self._ship(pi, int(tok), float(lp))

    def _ship(self, pi: int, tok: int, lp: float) -> None:
        """Prefill finished: emit the first token (TTFT is prefill
        completion), encode the prompt's KV blocks onto the edge, hand
        the blocks back to the prefill pool (the payload is the copy
        in flight; the prefix index keeps its own refs)."""
        s = self.psched.slots[pi]
        req = s.request
        self._emit_first(req, tok, lp)
        if not req.done:
            nb = len(s.blocks)
            # page_arrays is the tier-aware whole-page read: at
            # tiers == 1 it is the direct gather this always was; a
            # tiered prefill pool promotes the blocks hot first so the
            # wire carries exact-dtype bytes, never double-quantized
            # cold pages.
            kb, vb = self.prefill_pool.page_arrays(s.blocks)
            # kb/vb: (L, nb, bs, KV*hd)
            # Zero the garbage tail of the last block: stale positions
            # would pollute the int8 per-block quantization scales.
            valid = (np.arange(nb * self.block_size)
                     < req.prompt.size).reshape(nb, self.block_size)
            mask = jnp.asarray(valid)[None, :, :, None]
            kb = jnp.where(mask, kb, 0)
            vb = jnp.where(mask, vb, 0)
            wire_k, n_k = self.edge.codec.encode(kb)
            wire_v, n_v = self.edge.codec.encode(vb)
            self.edge.send(KVTransfer(
                request=req, wire_k=wire_k, wire_v=wire_v, n_blocks=nb,
                length=int(req.prompt.size), pending_token=tok,
                nbytes=n_k + n_v))
            self.metrics.inc("fleet_shipped")
            self.metrics.observe("fleet_wire_bytes", n_k + n_v)
        if self.prefix is not None:
            self.prefix.register(req.prompt, s.blocks,
                                 ns=tenant_of(req))
        self.psched.retire(pi)

    def _emit_first(self, req: Request, tok: int, lp: float) -> None:
        req.tokens.append(tok)
        req.logprobs.append(lp)
        req.token_versions.append(self.param_version)
        now = time.perf_counter()
        req.token_times.append(now)
        req.first_token_at = now
        self.metrics.observe("serve_ttft_ms",
                             (now - req.submitted_at) * 1e3)
        if req.on_token is not None:
            req.on_token(tok)
        if req.max_new_tokens == 1 \
                or (req.eos_id is not None and tok == req.eos_id):
            req.done = True
            req.finished_at = now
            self.metrics.inc("serve_retired")

    # ---- degraded mode -------------------------------------------------

    def _degrade(self, req: Request) -> None:
        """Queue ``req`` for local re-prefill on the decode worker."""
        self.dsched.enqueue(req)
        self.metrics.inc("fleet_degraded")

    def _fail_prefill(self, exc: Exception) -> None:
        """The prefill worker died mid-chunk: reap EVERYTHING it owned
        — its slot, its queue, and every transfer still on the edge —
        and replay all of it through local chunked prefill. The
        prefill pool (and the prefix index rooted in it) dies with the
        worker; later submits route straight to the fallback."""
        warnings.warn(
            f"prefill worker failed ({type(exc).__name__}: {exc}); "
            "falling back to local chunked prefill on the decode "
            "worker", stacklevel=3)
        self.prefill_degraded = True
        self.metrics.inc("fleet_prefill_failures")
        harvested = []
        for i, s in enumerate(self.psched.slots):
            if s is not None:
                harvested.append(s.request)
                self.psched.retire(i)  # host bookkeeping; pool is dead
        harvested.extend(self.psched.queue)
        self.psched.queue.clear()
        while self.edge.queue:  # reap pending-edge state
            t = self.edge.queue.popleft()
            self.edge.dropped += 1
            harvested.append(t.request)
        self.prefix = None  # rooted in the dead prefill pool
        for req in sorted(harvested, key=lambda r: r.rid):
            if not req.done:
                self._degrade(req)

    def _run_degraded_chunk(self, di: int) -> None:
        """One local prefill chunk against the DECODE pool — the same
        jitted prefill program at the decode pool's shapes, so the
        recomputed KV (and the stateless-sampled first token) is
        bitwise what the healthy path would have produced."""
        s = self.dsched.slots[di]
        req = s.request
        start, C = s.prefill_done, self.prefill_chunk
        chunk = np.zeros((1, C), np.int32)
        piece = req.prompt[start:start + C]
        chunk[0, :piece.size] = piece
        k, v, tok, lp = self._prefill(
            self._decode_params, self.pool.k, self.pool.v,
            jnp.asarray(self._table_for(s)), jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(req.prompt.size),
            jnp.float32(req.temperature), jnp.int32(req.seed))
        self.pool.commit(k, v)
        s.prefill_done = min(start + C, int(req.prompt.size))
        s.length = s.prefill_done
        if s.prefill_done >= req.prompt.size:
            if not req.tokens:
                # Prefill-death replay: the first token was never
                # emitted — emit it now (TTFT is prefill completion).
                self._emit_first(req, int(tok), float(lp))
            # else: edge-drop replay — the first token already
            # streamed at _ship time; the recomputed sample is
            # bitwise identical (stateless (seed, position) keying)
            # and is dropped, never double-emitted.
            s.phase = "decode"
            s.generated = len(req.tokens)
            s.pending_token = req.tokens[-1] if req.tokens else int(tok)
            if req.done:  # max_new_tokens == 1 or instant EOS
                self.dsched.retire(di)

    def _promote_degraded(self) -> None:
        """Hand a locally re-prefilled sequence to the decode
        scheduler as soon as it has a free slot: ownership of the
        blocks transfers (both schedulers draw on the decode pool),
        and the slot starts in the decode phase exactly like an
        adopted transfer."""
        for i, s in enumerate(self.dsched.slots):
            if s is not None and s.phase == "decode" \
                    and self.sched.live < self.num_slots:
                st = self.dsched.release(i)
                self.sched.place(st.request, st.blocks, st.length,
                                 st.pending_token)
                self.metrics.inc("fleet_degraded_promoted")

    # ---- decode role ---------------------------------------------------

    def _pop_adoptable(self) -> KVTransfer | None:
        """FIFO edge delivery, gated by the decode scheduler's
        reservation rule (a free slot AND the full worst case fits).
        A transfer lost in flight (the ``edge-drop`` chaos drill)
        degrades to local re-prefill instead of vanishing."""
        if not self.edge.queue:
            return None
        if self.sched.live >= self.num_slots:
            return None
        t = self.edge.queue[0]
        need = self.sched.worst_case_blocks(t.request)
        if need > self.sched.pool_budget:
            return None
        t = self.edge.pop()
        if self.chaos is not None \
                and self.chaos.edge_drop_fires(self.edge.delivered):
            warnings.warn(
                f"KV transfer for request {t.request.rid} lost on the "
                "edge; re-prefilling locally on the decode worker",
                stacklevel=3)
            self.edge.dropped += 1
            self.metrics.inc("fleet_edge_failures")
            self._degrade(t.request)
            return None
        return t

    def _land(self, t: KVTransfer, dslots: list) -> None:
        """Adopt a transfer's blocks into the decode pool — fused into
        the decode step when a live batch exists, a standalone scatter
        otherwise — then place the slot."""
        ids = [self.pool.alloc() for _ in range(t.n_blocks)]
        adopt_ids = jnp.asarray(np.asarray(ids, np.int32))
        ak = EdgeCodec.decode(t.wire_k)
        av = EdgeCodec.decode(t.wire_v)
        if dslots:
            tables, lengths, last, temps, seeds = \
                self._bank_inputs(dslots)
            self._maybe_poison(dslots)
            k, v, toks, lps, bad = self._adopt_decode(
                self._decode_params, self.pool.k, self.pool.v,
                adopt_ids,
                ak, av, tables, lengths, last, temps, seeds)
            self.pool.commit(k, v)
            self._emit_bank(dslots, toks, lps, bad)
        else:
            self.pool.commit(
                self.pool.k.at[:, adopt_ids].set(
                    ak.astype(self.pool.k.dtype)),
                self.pool.v.at[:, adopt_ids].set(
                    av.astype(self.pool.v.dtype)))
        self.sched.place(t.request, ids, t.length, t.pending_token)
        self.metrics.inc("fleet_adopted")

    def _bank_inputs(self, dslots: list):
        S, BPS = self.num_slots, self.blocks_per_seq
        tables = np.zeros((S, BPS), np.int32)
        lengths = np.zeros(S, np.int32)
        last = np.zeros(S, np.int32)
        temps = np.zeros(S, np.float32)
        seeds = np.zeros(S, np.int32)
        for i in dslots:
            self.sched.ensure_block(i)
            s = self.sched.slots[i]
            tables[i] = self._table_for(s)
            lengths[i] = s.length
            last[i] = s.pending_token
            temps[i] = s.request.temperature
            seeds[i] = s.request.seed
        return (jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(last), jnp.asarray(temps),
                jnp.asarray(seeds))

    def _maybe_poison(self, dslots: list) -> None:
        """The ``nonfinite-logits`` drill on the disagg decode worker
        (see ServeEngine._maybe_poison): NaN one live request's
        private last KV block host-side."""
        if self.chaos is None or not dslots \
                or not self.chaos.poison_fires(self._step_n):
            return
        s = self.sched.slots[dslots[0]]
        blk = s.blocks[-1]
        self.pool.v = self.pool.v.at[:, blk].set(jnp.nan)

    def _run_decode_step(self, dslots: list) -> None:
        from tpu_ddp.serve.engine import _build_decode_step
        tables, lengths, last, temps, seeds = self._bank_inputs(dslots)
        self._maybe_poison(dslots)
        step = _build_decode_step(self.model, self.block_size,
                                  self.blocks_per_seq)
        k, v, toks, lps, bad = step(
            self._decode_params, self.pool.k, self.pool.v,
            tables, lengths, last, temps, seeds)
        self.pool.commit(k, v)
        self._emit_bank(dslots, toks, lps, bad)

    def _emit_bank(self, dslots: list, toks, lps, bad) -> None:
        toks, lps = np.asarray(toks), np.asarray(lps)
        bad = np.asarray(bad)
        for i in dslots:
            s = self.sched.slots[i]
            req = s.request
            if bad[i]:
                # Quarantine the poisoned request, not the bank:
                # scrub its private pages (a NaN'd page re-issued to
                # another request would leak through zero-weight
                # attention) and finish it flagged.
                self.pool.scrub([b for b in s.blocks
                                 if self.pool.refcount(b) == 1])
                self.sched.retire(i)
                req.quarantined = True
                req.done = True
                req.finished_at = time.perf_counter()
                self.metrics.inc("serve_quarantined")
                warnings.warn(
                    f"request {req.rid}: non-finite logits at engine "
                    f"step {self._step_n}; request quarantined",
                    stacklevel=3)
                continue
            s.length += 1
            tok = int(toks[i])
            s.generated += 1
            s.pending_token = tok
            req.tokens.append(tok)
            req.logprobs.append(float(lps[i]))
            req.token_versions.append(self.param_version)
            req.token_times.append(time.perf_counter())
            if req.on_token is not None:
                req.on_token(tok)
            if s.generated >= req.max_new_tokens \
                    or (req.eos_id is not None and tok == req.eos_id):
                req.done = True
                req.finished_at = time.perf_counter()
                self.sched.retire(i)
                self.metrics.inc("serve_retired")

    # ---- introspection -------------------------------------------------

    def accounting_ok(self) -> bool:
        # The decode pool has TWO schedulers drawing on it (sched +
        # the degraded-prefill fallback), so its identity is checked
        # over their joint holders. The prefill pool's check is
        # skipped once its worker died — that hardware (and its
        # accounting) is gone from the system.
        holders = [s.blocks for s in self.sched.slots if s is not None]
        holders += [s.blocks for s in self.dsched.slots
                    if s is not None]
        if not self.pool.refcount_ok(holders):
            return False
        return self.prefill_degraded or self.psched.accounting_ok()

    def drain(self) -> list[Request]:
        """Harvest every unfinished request from the whole pipeline
        (queues, prefill slot, edge, fallback, decode slots) and
        release all engine state — the router's failure-migration
        hook. Submit order."""
        reqs = list(self.psched.queue)
        self.psched.queue.clear()
        reqs.extend(self.dsched.queue)
        self.dsched.queue.clear()
        for sched in (self.psched, self.dsched, self.sched):
            for i, s in enumerate(sched.slots):
                if s is not None:
                    reqs.append(s.request)
                    sched.retire(i)
        while self.edge.queue:
            t = self.edge.queue.popleft()
            self.edge.dropped += 1
            reqs.append(t.request)
        return sorted((r for r in reqs if not r.done),
                      key=lambda r: r.rid)

    def lower_adopt_decode(self, n_blocks: int = 2):
        """``jit.lower`` the fused adopt+decode program for a
        representative transfer size — the audit surface
        ``tpu_ddp/analysis`` fingerprints and donation-checks."""
        sds = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
            jnp.shape(x), jnp.result_type(x))
        params = jax.tree.map(sds, self._decode_params)
        S, BPS = self.num_slots, self.blocks_per_seq
        pk = sds(self.pool.k)
        payload = jax.ShapeDtypeStruct(
            (self.model.num_layers, n_blocks, self.block_size,
             self.model.kv_heads * self.model.head_dim), jnp.float32)
        i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        return self._adopt_decode.lower(
            params, pk, pk, i32((n_blocks,)), payload, payload,
            i32((S, BPS)), i32((S,)), i32((S,)),
            jax.ShapeDtypeStruct((S,), jnp.float32),
            i32((S,)))

    def adopt_decode_hlo(self, n_blocks: int = 2) -> str:
        """Compiled HLO of the fused adopt+decode program — what
        ``tpu_ddp/analysis`` (assert_transfer_overlap) scans."""
        return self.lower_adopt_decode(n_blocks).compile().as_text()

    def lower_degraded_prefill(self):
        """``jit.lower`` the degraded-mode local prefill: the SAME
        prefill program traced at the DECODE pool's shapes (more
        blocks than the prefill pool), i.e. a distinct compiled
        program — the graph-audit cell for the fallback path."""
        sds = jax.ShapeDtypeStruct
        return self._prefill.lower(
            self._decode_params, self.pool.k, self.pool.v,
            sds((self.blocks_per_seq,), jnp.int32),
            sds((1, self.prefill_chunk), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((), jnp.float32), sds((), jnp.int32))


__all__ = ["DisaggEngine", "KVEdge", "KVTransfer"]
