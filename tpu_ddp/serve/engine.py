"""The serving engine: request lifecycle over the paged KV pool and the
continuous-batching scheduler.

One ``ServeEngine`` owns a dense model + params, a ``PagedKVPool``, a
``Scheduler`` and TWO jitted programs, compiled once each (a third —
the fused speculative step, tpu_ddp/serve/speculative.py — joins
only under ``spec_k > 0``):

- ``decode step`` — one token for the ENTIRE slot bank per call.
  Static (num_slots, blocks_per_seq) shapes; idle slots ride along with
  zeroed block tables, so their scatters land in the null block and
  their sampled outputs are discarded host-side. Per layer it is the
  shared decode core (tpu_ddp/models/decode.py project_qkv /
  block_finish) around attention over the paged pool: READ IN PLACE
  by the paged kernel (ops/pallas/paged_attention.py) where its
  predicate takes the shapes, so a step's K/V bytes follow the live
  context, and otherwise ``attend_cached`` over a pool-GATHERED
  ``max_seq_len`` view. Both are the math ``generate()`` runs over
  contiguous buffers, which is what makes the engine-vs-generate
  parity tests meaningful.
- ``prefill step`` — ONE ``prefill_chunk``-token slice of ONE prompt
  per call, every chunk the same static shape (short chunks padded;
  padded positions scatter to the null block and their outputs are
  masked by the causal position test). Chunking bounds how long a
  long prompt can stall the decode batch: one chunk per engine step.
  Each layer gathers the slot's pages from the whole pool in one
  gather (kv_pool.gather_view) and attends over the view in as many
  equal parts of the chunk as keep a part's float32 scores inside the
  chip's on-chip memory (:func:`attend_chunk`; one part at most sizes).

The plain engine (``spec_k == 0``) runs ONE DECODE STEP AHEAD of its
readback: a step dispatches its prefill chunk and its decode program
first and only then reads the step before it back, so the device goes
from one program to the next while the host emits, schedules and builds
tables beside it. The one datum a step needs from the step before, each
slot's sampled token, stays on the device (``serve_feed``); docs/DESIGN.md
§19 has the rules.

Token positions are written BEFORE they are attended (the new token's
K/V is scattered, then the pool or its gathered view is attended), so
a query never reads an unwritten slot of its own sequence; everything
beyond a query's position gets an exact zero weight (the kernel's
length mask, decode.attend_cached's causal mask).

A model may keep, in some layers, a fixed-size recurrent state per
sequence and no K/V (``model.mixers``): the paged pool then holds pages
for the attention layers only, and a ``StatePool`` indexed by slot lives
beside it, donated through both step programs and updated in place like
the pool (docs/DESIGN.md §31 has the three rules that take the place of
the null block there). A model may keep such state in every layer and
no K/V at all (power retention, models/retention.py): its pool then has
0 layers and 0 bytes, its block tables only mark live slots, and the
step programs donate the state alone (docs/DESIGN.md §33).

A model may attend, in some layers, over a learned selection of the
cached positions (``"sparse_attention"``, models/sparse_moe.py): those
layers also cache an index key per position, in the pool's ``index``
pages at the same block ids, donated through both programs beside the
state's arrays. A decode step scores every position of the slot, keeps
the top ``index_topk`` and gathers only their K/V rows
(:func:`sparse_step`); a prefill chunk scores its queries against the
gathered view and attends over it with the selection as the mask
(:func:`sparse_chunk`). docs/DESIGN.md §32.

Sampling is per-request and stateless (decode.sample_token): keyed by
(request seed, absolute position), so a request replayed after
cancellation or across engines reproduces its tokens exactly.

Checkpoints load via the canonical utils/checkpoint.py path —
:meth:`ServeEngine.from_checkpoint` is
``dense_params_from_checkpoint`` + construction, the train→serve
round trip in one call.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import math
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpu_ddp.models.decode import (
    attend_cached,
    attend_masked,
    attn_scale,
    block_finish,
    check_decodable,
    check_state_servable,
    dense_params_from_checkpoint,
    mlp_half,
    project_qkv,
    residual,
    sample_token,
    ssm_mix,
)
from tpu_ddp.models.hybrid import advance_state
from tpu_ddp.models.retention import (
    RETENTION,
    retention_chunk,
    retention_step,
)
from tpu_ddp.models.sparse_moe import (
    index_scores,
    select_top,
    selection_mask,
)
from tpu_ddp.ops.pallas import (
    paged_attention,
    sparse_prefill_attention,
    ssm_state_step,
)
from tpu_ddp.serve.kv_pool import (
    PagedKVPool,
    StatePool,
    gather_view,
    kv_layers,
    pin_committed,
    rows,
)
from tpu_ddp.serve.speculative import (
    accept_length,
    build_spec_step,
    parse_spec_draft,
)
from tpu_ddp.serve.scheduler import (
    Scheduler,
    parse_tenant_classes,
    tenant_of,
)
from tpu_ddp.utils.metrics import MetricsLogger
from tpu_ddp.utils.profiling import (
    SERVE_DECODE,
    SERVE_FEED,
    SERVE_PREFILL,
    TALLY_S,
    burst,
    mark,
    program,
    span,
)


@dataclasses.dataclass
class Request:
    """One submitted request; doubles as the caller's streaming handle
    (the engine appends into ``tokens``/``logprobs`` as they land)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    eos_id: int | None = None
    on_token: Callable[[int], None] | None = None
    # Multi-tenancy (§25): the tenant namespace this request bills to —
    # WFQ class, prefix-cache namespace, and per-tenant accounting all
    # key on it. "default" keeps single-tenant call sites unchanged.
    tenant: str = "default"
    tokens: list = dataclasses.field(default_factory=list)
    logprobs: list = dataclasses.field(default_factory=list)
    # Param version each token was sampled under (tpu_ddp/publish/):
    # one stamp per token — a weight-stream flip lands BETWEEN engine
    # steps, so no token ever mixes versions, and the stream's stamps
    # are non-decreasing (loadgen.assert_atomic_cutover pins both).
    token_versions: list = dataclasses.field(default_factory=list)
    # Wall-clock stamp per emitted token (perf_counter) — the honest
    # TPOT basis under speculation, where one engine step can emit a
    # burst of tokens (loadgen computes inter-token percentiles from
    # these stamps, never from a tokens-per-step assumption).
    token_times: list = dataclasses.field(default_factory=list)
    # Speculation ledger (§26): per-request proposal accounting with
    # the identity proposed == accepted + rejected at every step.
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_rejected: int = 0
    done: bool = False
    cancelled: bool = False
    shed: bool = False          # dropped by admission control (SLO)
    quarantined: bool = False   # non-finite logits: request isolated
    migrations: int = 0         # times replayed on another replica
    submitted_at: float = 0.0
    first_token_at: float | None = None
    finished_at: float | None = None

    @property
    def ttft_s(self) -> float | None:
        """Time to first token (seconds since submit), once known."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


def kv_write(pool_k, pool_v, li: int, bidx, off, k, v):
    """Scatter the new ``k`` / ``v`` (n, KV, hd) into layer ``li``'s
    pages at ``(bidx, off)``, under the scope ``kv_write``."""
    with jax.named_scope("kv_write"):
        pool_k = pool_k.at[li, bidx, off].set(
            rows(k).astype(pool_k.dtype))
        pool_v = pool_v.at[li, bidx, off].set(
            rows(v).astype(pool_v.dtype))
    return pool_k, pool_v


def paged_kv(model, pool_k, pool_v, li: int, bidx, off, k, v, tables):
    """Layer ``li``'s half of the paged cache, the gather form: write
    the new rows (:func:`kv_write`), then gather the layer's pages
    through ``tables`` (S, BPS) into the contiguous (S, BPS*block_size,
    KV, hd) view the decode core attends over (scope ``kv_gather``).
    Both halves index the whole pool, a scatter at ``[li, bidx, off]``
    and one gather at ``[li, tables]``: the pool stays the donated
    argument updated in place, and no copy of a layer is made to read
    ``BPS`` pages of it (kv_pool.gather_view).
    The prefill chunk runs it, and the decode step where the paged
    kernel does not take the shapes, so a trace names the two halves
    the same way in both."""
    pool_k, pool_v = kv_write(pool_k, pool_v, li, bidx, off, k, v)
    with jax.named_scope("kv_gather"):
        ck = gather_view(pool_k, li, tables, model)
        cv = gather_view(pool_v, li, tables, model)
    return pool_k, pool_v, ck, cv


def _keep(active, new, old):
    """Rule 1 of the three (module docstring of kv_pool.StatePool):
    ``new`` for the slots that have a row (``active`` (S,)), ``old``,
    bit for bit, for the others."""
    return jnp.where(active.reshape((-1,) + (1,) * (old.ndim - 1)),
                     new.astype(old.dtype), old)


def state_step(model, blk, x, state, si: int, active):
    """A state-space layer of the decode step: state layer ``si`` of the
    pool for the whole bank, advanced by one token where ``active`` (S,)
    and left as it was elsewhere (rule 1 of three, module docstring of
    kv_pool.StatePool: a slot that is idle, or between two prefill
    chunks, rides along as a row and must not be touched). Scope ``ssm``,
    the pool's reads and writes under ``ssm/state``.

    The recurrence's ``S`` is advanced where it lies, read once and
    written once, by the kernel (ops/pallas/ssm_state_step.py) where its
    predicate over shapes and dtype takes the pool; otherwise by the
    plain body, which slices the layer out, advances it, and writes it
    back through a select. The choice is made here, as the program is
    traced."""
    ssm, conv = state["ssm"], state["conv"]

    def in_pool(pool, *step):
        with jax.named_scope("state"):
            return ssm_state_step.ssm_state_step(pool, *step, layer=si,
                                                 active=active)

    def plain(pool, *step):
        with jax.named_scope("state"):
            old = pool[si]
        y, new = advance_state(old, *step)
        with jax.named_scope("state"):
            return y, pool.at[si].set(_keep(active, new, old))

    takes = ssm_state_step.supports(ssm.shape[3], ssm.shape[4], ssm.dtype)
    with jax.named_scope("ssm"):
        with jax.named_scope("state"):
            old_conv = conv[si]
        x, ssm, new_conv = ssm_mix(model, blk, x, ssm, old_conv,
                                   advance=in_pool if takes else plain)
        with jax.named_scope("state"):
            conv = conv.at[si].set(_keep(active, new_conv, old_conv))
    return mlp_half(model, blk, x), {"ssm": ssm, "conv": conv}


def retention_state_step(model, blk, x, state, si: int, active, pos):
    """A power-retention layer of the decode step (scope ``retention``):
    state layer ``si`` of the pool for the whole bank at positions ``pos``
    (S, 1), under rule 1 as in :func:`state_step`. Norm, projections and
    gate, then under ``retention/state`` the slots' ``S`` and ``z`` read
    out of the pool, the readout from them and the update written back
    where the slot has a row (models/retention.py ``retention_step``: the
    readout and the update each read the layer's state once, and the
    update writes it once, in place), then the output projection and the
    residual."""
    with jax.named_scope("retention"):
        y = model.norm(x, blk["ln1"])
        q, k, v = model.qkv_proj(blk, y, pos)
        a = model.gate(blk, y)
        with jax.named_scope("state"):
            S, z = state["S"], state["z"]
            o, new_S, new_z = retention_step(model, q[:, 0], k[:, 0],
                                             v[:, 0], a[:, 0], S[si], z[si])
            S = S.at[si].set(_keep(active, new_S, S[si]))
            z = z.at[si].set(_keep(active, new_z, z[si]))
        x = residual(model, x, model.out_proj(blk, o[:, None]))
    return mlp_half(model, blk, x), {"S": S, "z": z}


def _slot_state(state, si: int, slot, fresh) -> dict:
    """Slot ``slot``'s state in state layer ``si``, each array of
    ``state``: zero where ``fresh`` (rule 2: a request's first chunk,
    whatever the slot's last tenant left there)."""
    return {k: jnp.where(fresh, 0, lax.dynamic_slice(
        a, (si, slot) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:])[0, 0])
        .astype(a.dtype) for k, a in state.items()}


def _put_slot_state(state, new: dict, si: int, slot) -> dict:
    """``state`` with slot ``slot``'s state in layer ``si`` set to
    ``new``, in place."""
    return {k: lax.dynamic_update_slice(
        a, new[k].astype(a.dtype)[None, None],
        (si, slot) + (0,) * (a.ndim - 2)) for k, a in state.items()}


def state_chunk(model, blk, x, state, si: int, slot, fresh, n_valid):
    """A state-space layer of a prefill chunk: slot ``slot``'s state in
    state layer ``si``, from zero where ``fresh`` (rule 2: a request's
    first chunk, whatever the slot's last tenant left there), advanced
    by the chunk's first ``n_valid`` rows and not by its padding (rule
    3, models/hybrid.py ``ssm_chunk``)."""
    with jax.named_scope("ssm"):
        with jax.named_scope("state"):
            old = _slot_state(state, si, slot, fresh)
        x, ssm, conv = ssm_mix(model, blk, x, old["ssm"], old["conv"],
                               n_valid)
        with jax.named_scope("state"):
            state = _put_slot_state(state, {"ssm": ssm, "conv": conv}, si,
                                    slot)
    return mlp_half(model, blk, x), state


def retention_state_chunk(model, blk, x, state, si: int, slot, fresh,
                          n_valid, pos):
    """A power-retention layer of a prefill chunk ``x`` (1, C, dm) at
    positions ``pos`` (scope ``retention``), under rules 2 and 3 as in
    :func:`state_chunk`: the slot's incoming state read (from zero where
    ``fresh``) and the outgoing one written under ``retention/carry``,
    with the part of the chunked form that reads and makes the state;
    the quadratic part within the chunk under ``retention/chunk``
    (models/retention.py ``retention_chunk``). Rule 3: a padding row's
    gate is 1 and its key and value are zero, so it advances nothing."""
    C = x.shape[1]
    with jax.named_scope("retention"):
        y = model.norm(x, blk["ln1"])
        q, k, v = model.qkv_proj(blk, y, pos)
        valid = jnp.arange(C) < n_valid
        k = jnp.where(valid[:, None, None], k[0], 0)
        v = jnp.where(valid[:, None, None], v[0], 0)
        a = jnp.where(valid[:, None], model.gate(blk, y)[0], 0.0)
        with jax.named_scope("carry"):
            old = _slot_state(state, si, slot, fresh)
        o, S, z = retention_chunk(model, q[0], k, v, a, old["S"], old["z"])
        with jax.named_scope("carry"):
            state = _put_slot_state(state, {"S": S, "z": z}, si, slot)
        x = residual(model, x, model.out_proj(blk, o[None]))
    return mlp_half(model, blk, x), state


def index_write(index, xi: int, bidx, off, keys, run: bool = False):
    """Write the index keys ``keys`` (n, Di) into sparse layer ``xi``'s
    pages at ``(bidx, off)``: a key's ``Di`` lanes of its page's row
    (serve/kv_pool.py ``index_page``), one windowed scatter. ``run``: the
    keys are of consecutive positions from a row's first, a whole number
    of rows (a prefill chunk), and are written a row at a time, as K/V
    are."""
    n, di = keys.shape
    per_row = index.shape[-1] // di
    if run:
        return index.at[xi, bidx[::per_row], off[::per_row] // per_row].set(
            keys.reshape(n // per_row, -1).astype(index.dtype))
    at = jnp.stack([jnp.full_like(bidx, xi), bidx, off // per_row,
                    off % per_row * di], axis=-1)
    dims = lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2, 3))
    return lax.scatter(index, at, keys.astype(index.dtype), dims,
                       mode=lax.GatherScatterMode.FILL_OR_DROP)


def index_view(index, xi: int, tables):
    """Sparse layer ``xi``'s index-key pages gathered through ``tables``
    (S, BPS): (S, rows, width), the keys of positions ``0..BPS*block_size
    - 1`` in order, as :func:`sparse_moe.index_scores` reads them."""
    pages = index[xi, tables]
    return pages.reshape(pages.shape[0], -1, pages.shape[-1])


def sparse_step(model, blk, x, pool_k, pool_v, index, li: int, xi: int,
                bidx, off, tables, lengths, block_size: int):
    """A sparse-attention layer of the decode step, for the whole bank:
    the new K/V and index key written at ``(bidx, off)``; under the scope
    ``attn/index`` the indexer's projections, every position of each
    slot's table scored against the slot's query (the index pages
    gathered through ``tables``) and the top ``index_topk`` at positions
    <= ``lengths`` kept; under ``attn/sparse`` only those positions' K/V
    rows gathered from the pool and attended. ``li`` / ``xi``: the
    layer's place among the K/V and the index pages. Returns (x, pool_k,
    pool_v, index)."""
    pos = lengths[:, None]
    with jax.named_scope("attn"):
        y = model.norm(x, blk["ln1"])
        q, k, v = model.qkv_proj(blk, y, pos)
        with jax.named_scope("index"):
            qi, ki, w = model.index_proj(blk, y, pos)
        pool_k, pool_v = kv_write(pool_k, pool_v, li, bidx, off, k[:, 0],
                                  v[:, 0])
        with jax.named_scope("kv_write"):
            index = index_write(index, xi, bidx, off, ki[:, 0])
        with jax.named_scope("index"):
            sel, keep = select_top(
                model, index_scores(model, qi, w,
                                    index_view(index, xi, tables)), pos)
        with jax.named_scope("sparse"):
            sel = sel[:, 0]                                     # (S, K)
            at = (jnp.take_along_axis(tables, sel // block_size, axis=1),
                  sel % block_size)
            shape = sel.shape + (model.kv_heads, model.head_dim)
            ck = pool_k[li, at[0], at[1]].reshape(shape)
            cv = pool_v[li, at[0], at[1]].reshape(shape)
            o = attend_masked(model, q, ck, cv, keep)
    return block_finish(model, blk, x, o), pool_k, pool_v, index


def sparse_kernel_takes(model, C: int, T: int, kv_dtype) -> bool:
    """Does a sparse-attention layer's prefill chunk of ``C`` queries
    attend over its ``T``-position view of ``kv_dtype`` through the
    kernel (ops/pallas/sparse_prefill_attention.py)? Shapes and dtypes
    only, and the K/V in the compute dtype."""
    return jnp.dtype(kv_dtype) == jnp.dtype(model.compute_dtype) \
        and sparse_prefill_attention.supports(
            C, T, model.num_heads, model.kv_heads, model.head_dim, kv_dtype)


def sparse_chunk(model, blk, x, pool_k, pool_v, index, li: int, xi: int,
                 blk_idx, off, table, p):
    """A sparse-attention layer of a prefill chunk ``x`` (1, C, dm) at
    positions ``p``: the chunk's K/V and index keys written, the slot's
    pages gathered (under ``kv_gather`` as every chunk does), every query
    of the chunk scored against every position and its selection made
    under ``attn/index`` (the scores in parts, float32 within
    ``PREFILL_SCORES_BYTES``; the selection for the whole chunk at once),
    and the chunk attended over the gathered view with its selection as
    the mask: in one pass of the kernel where :func:`sparse_kernel_takes`
    holds, else in :func:`attend_chunk`'s parts through
    ``attend_masked``. Returns (x, pool_k, pool_v, index)."""
    C = x.shape[1]
    with jax.named_scope("attn"):
        y = model.norm(x, blk["ln1"])
        q, k, v = model.qkv_proj(blk, y, p)
        with jax.named_scope("index"):
            qi, ki, w = model.index_proj(blk, y, p)
        pool_k, pool_v, ck, cv = paged_kv(model, pool_k, pool_v, li, blk_idx,
                                          off, k[0], v[0], table[None])
        with jax.named_scope("kv_write"):
            # a chunk starts at a multiple of its length
            index = index_write(index, xi, blk_idx, off, ki[0],
                                run=C % (index.shape[-1] // ki.shape[-1]) == 0)
        with jax.named_scope("kv_gather"):
            # one key a row, in the order of the positions: the chunk's
            # float32 scores then come out in that order, part by part
            keys = index_view(index, xi, table[None]).reshape(
                1, -1, model.index_dim)
        T = ck.shape[1]
        with jax.named_scope("index"):
            n = C // _parts(C, 4 * model.index_heads * T)
            scores = lax.map(
                lambda a: index_scores(model, a[0][None], a[1][None],
                                       keys)[0],
                (qi[0].reshape((C // n, n) + qi.shape[2:]),
                 w[0].reshape((C // n, n) + w.shape[2:])))
            keep = selection_mask(model, scores.reshape(1, C, T), p[None])
        if sparse_kernel_takes(model, C, T, ck.dtype):
            o = sparse_prefill_attention.sparse_prefill_attention(
                q[0], ck[0], cv[0], keep[0], scale=attn_scale(model))
        else:
            n = C // _parts(C, 4 * q.shape[2] * T)
            o = lax.map(
                lambda a: attend_masked(model, a[0][None], ck, cv,
                                        a[1][None])[0],
                (q[0].reshape((C // n, n) + q.shape[2:]),
                 keep[0].reshape(C // n, n, T)))
    return (block_finish(model, blk, x, o.reshape(q.shape)), pool_k, pool_v,
            index)


# What one ``attend_cached`` call of a prefill chunk may hold as float32
# scores, (heads, queries, keys): half of the 128 MiB of on-chip memory
# beside a v5e's HBM. Whether the compiler keeps the scores there or in
# HBM is its own choice, made for the whole program, and at a size near
# the whole of that memory it flips with the program's depth (the
# benchmark's chunk, 24 heads x 256 x 4096, is 100 MB: on-chip in a
# 28-layer program, in HBM, written once and read twice, in a 29-layer
# one; DESIGN.md §19). At half of it the scores stay on-chip.
PREFILL_SCORES_BYTES = 64 << 20


def _parts(C: int, row_bytes: int) -> int:
    """Into how many equal parts a chunk of ``C`` queries is cut so that
    a part's float32 scores, ``row_bytes`` a query, stay within
    ``PREFILL_SCORES_BYTES``: halved while they do not and C allows."""
    parts = 1
    while C * row_bytes > parts * PREFILL_SCORES_BYTES \
            and C % (2 * parts) == 0:
        parts *= 2
    return parts


def attend_chunk(model, q, ck, cv, p):
    """``attend_cached`` for a prefill chunk's queries ``q`` (1, C, H,
    hd) at positions ``p`` (C,), over equal parts of the chunk where the
    scores of all of it would pass ``PREFILL_SCORES_BYTES``: a query
    row's result does not depend on the rows beside it, so the parts
    give what the whole gives."""
    C = q.shape[1]
    parts = _parts(C, 4 * q.shape[0] * q.shape[2] * ck.shape[1])
    if parts == 1:
        return attend_cached(model, q, ck, cv, p)
    n = C // parts
    return jnp.concatenate(
        [attend_cached(model, q[:, i:i + n], ck, cv, p[i:i + n])
         for i in range(0, C, n)], axis=1)


def decode_bank(model, block_size: int, params, pool_k, pool_v, tables,
                lengths, last_tokens, temps, seeds, state=None):
    """The traced body of the whole-bank decode step — one token for
    every live slot. Module-level (not a closure) so the disagg fused
    adopt+decode program (tpu_ddp/fleet/disagg.py) can prepend its
    KV-block adoption scatter and reuse the identical decode math —
    bitwise parity between fleet and single-engine output depends on
    there being exactly ONE implementation of this body.

    Attention reads the pool in place through the paged kernel
    (ops/pallas/paged_attention.py) where its predicate over shapes and
    dtypes takes this model, block size and pool — a step's K/V bytes
    then follow the live context — and through the gathered
    ``max_seq_len`` view otherwise. The choice is made here, as the
    program is traced; both bodies attend positions ``0..lengths``.

    ``state``: the state pool's arrays, for a model with layers that keep
    recurrent state; it is then returned last, advanced for the slots
    that have a row. Those are the slots whose table row is not all null:
    an idle slot's, and one's between two prefill chunks, is zeros. For a
    model with sparse-attention layers it holds the index-key pages under
    ``"index"`` (:func:`sparse_step`), returned with the new keys."""
    cd = model.compute_dtype
    in_place = paged_attention.supports(model.head_dim, block_size,
                                        pool_k.dtype, cd)
    with jax.named_scope("embed"):
        x = model.embed(params, last_tokens[:, None])     # (S, 1, dm)
    pos = lengths[:, None]                                # (S, 1)
    bidx = jnp.take_along_axis(
        tables, (lengths // block_size)[:, None], axis=1)[:, 0]
    off = lengths % block_size
    active = tables[:, 0] != PagedKVPool.NULL_BLOCK
    li = si = xi = 0     # the layer's place among those of its kind
    for blk, mixer in zip(params["blocks"], model.mixers):
        if mixer == "mamba":
            x, state = state_step(model, blk, x, state, si, active)
            si += 1
            continue
        if mixer == RETENTION:
            x, state = retention_state_step(model, blk, x, state, si,
                                            active, pos)
            si += 1
            continue
        if mixer == "sparse_attention":
            x, pool_k, pool_v, index = sparse_step(
                model, blk, x, pool_k, pool_v, state["index"], li, xi, bidx,
                off, tables, lengths, block_size)
            state = dict(state, index=index)
            li, xi = li + 1, xi + 1
            continue
        with jax.named_scope("attn"):
            q, k, v = project_qkv(model, blk, x, pos)
            if in_place:
                pool_k, pool_v = kv_write(pool_k, pool_v, li, bidx, off,
                                          k[:, 0], v[:, 0])
                o = paged_attention.paged_decode_attention(
                    q[:, 0], pool_k, pool_v, tables, lengths + 1,
                    layer=li, kv_heads=model.kv_heads,
                    scale=attn_scale(model))[:, None]
            else:
                pool_k, pool_v, ck, cv = paged_kv(
                    model, pool_k, pool_v, li, bidx, off, k[:, 0],
                    v[:, 0], tables)
                o = attend_cached(model, q, ck, cv, pos)
        x = block_finish(model, blk, x, o)
        li += 1
    logits = model.head_apply(params, x)[:, 0]            # (S, V)
    with jax.named_scope("sample"):
        toks, lps = jax.vmap(
            lambda lg, t, sd, p: sample_token(model, lg, t, sd, p))(
                logits, temps, seeds, lengths + 1)
        # In-graph non-finite detection, the decode analog of
        # StepGuard's gradient check: a slot whose logits went NaN/Inf
        # (poisoned KV pages, numerical blow-up) is flagged so the host
        # quarantines exactly that request — never the whole bank.
        # Checking logits (not just the sampled logprob) catches an
        # isolated Inf the sampled position might miss.
        bad = ~(jnp.all(jnp.isfinite(logits), axis=-1)
                & jnp.isfinite(lps))
    if state is None:
        return pool_k, pool_v, toks, lps, bad
    return pool_k, pool_v, toks, lps, bad, state


def _carries_more(model) -> bool:
    """Do the step programs take a third donated argument beside the K/V
    pool: the state pool's arrays, the index-key pages, or both."""
    return bool(model.state_shapes(1)) or bool(getattr(model, "index_dim",
                                                       0))


def _donated(model) -> tuple:
    """What a step program of a model that carries more donates: the
    K/V pool and the third argument, or the third alone where the pool
    has no layer (a model whose every layer keeps state: its pool
    arrays hold 0 bytes and no layer reads or writes them)."""
    return (1, 2, 3) if kv_layers(model) else (3,)


# Both step builders are memoized on (model, block_size, blocks_per_seq)
# — model is a frozen dataclass, so the key is by-value. Every engine
# with the same cache geometry shares ONE compiled program; sweep
# scripts and tests construct engines freely without paying recompiles.
@functools.lru_cache(maxsize=32)
def _build_decode_step(model, block_size: int, blocks_per_seq: int):
    """One jitted token step for the whole slot bank. ``tables``
    (S, BPS) int32 block tables (zeros = null for idle slots),
    ``lengths`` (S,) cache positions written so far, ``last_tokens``
    (S,) the pending token each slot feeds at position ``lengths``."""

    if _carries_more(model):
        # the state pool's arrays ride beside the K/V pool's, donated
        @program(SERVE_DECODE)
        def step(params, pool_k, pool_v, state, tables, lengths,
                 last_tokens, temps, seeds):
            return decode_bank(model, block_size, params, pool_k, pool_v,
                               tables, lengths, last_tokens, temps, seeds,
                               state=state)

        return jax.jit(step, donate_argnums=_donated(model))

    @program(SERVE_DECODE)
    def step(params, pool_k, pool_v, tables, lengths, last_tokens,
             temps, seeds):
        return decode_bank(model, block_size, params, pool_k, pool_v,
                           tables, lengths, last_tokens, temps, seeds)

    return jax.jit(step, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=32)
def _build_prefill_step(model, block_size: int, blocks_per_seq: int):
    """One jitted prefill chunk for ONE slot. ``tokens`` (1, C) is the
    chunk (zero-padded past the prompt), occupying absolute positions
    ``start..start+C-1``; positions >= ``prompt_len`` scatter to the
    null block and never influence a valid query (causal mask). The
    sampled (token, logprob) pair is meaningful only on the final
    chunk (the one containing position ``prompt_len - 1``); earlier
    chunks compute and discard it so every chunk is ONE program.

    For a model with layers that keep recurrent state the program also
    takes the state pool's arrays (donated, returned last) and the
    ``slot`` the chunk belongs to: the state has no block table to find
    it by. A model with sparse-attention layers passes its index-key
    pages the same way (``state["index"]``)."""

    def chunk(params, pool_k, pool_v, table, tokens, start, prompt_len,
              temp, seed, state=None, slot=None):
        C = tokens.shape[1]
        p = start + jnp.arange(C)                             # (C,)
        valid = p < prompt_len
        safe = jnp.clip(p // block_size, 0, blocks_per_seq - 1)
        blk_idx = jnp.where(valid, table[safe], PagedKVPool.NULL_BLOCK)
        off = p % block_size
        with jax.named_scope("embed"):
            x = model.embed(params, tokens)                   # (1, C, dm)
        li = si = xi = 0
        for blkp, mixer in zip(params["blocks"], model.mixers):
            if mixer == "mamba":
                x, state = state_chunk(
                    model, blkp, x, state, si, slot, start == 0,
                    jnp.clip(prompt_len - start, 0, C))
                si += 1
                continue
            if mixer == RETENTION:
                x, state = retention_state_chunk(
                    model, blkp, x, state, si, slot, start == 0,
                    jnp.clip(prompt_len - start, 0, C), p)
                si += 1
                continue
            if mixer == "sparse_attention":
                x, pool_k, pool_v, index = sparse_chunk(
                    model, blkp, x, pool_k, pool_v, state["index"], li, xi,
                    blk_idx, off, table, p)
                state = dict(state, index=index)
                li, xi = li + 1, xi + 1
                continue
            with jax.named_scope("attn"):
                q, k, v = project_qkv(model, blkp, x, p)
                pool_k, pool_v, ck, cv = paged_kv(
                    model, pool_k, pool_v, li, blk_idx, off, k[0], v[0],
                    table[None])
                o = attend_chunk(model, q, ck, cv, p)
            x = block_finish(model, blkp, x, o)
            li += 1
        logits = model.head_apply(params, x)[0]               # (C, V)
        last = jnp.clip(prompt_len - 1 - start, 0, C - 1)
        with jax.named_scope("sample"):
            tok, lp = sample_token(model, logits[last], temp, seed,
                                   prompt_len)
        if state is None:
            return pool_k, pool_v, tok, lp
        return pool_k, pool_v, tok, lp, state

    if _carries_more(model):
        @program(SERVE_PREFILL)
        def step(params, pool_k, pool_v, state, table, tokens, start,
                 prompt_len, temp, seed, slot):
            return chunk(params, pool_k, pool_v, table, tokens, start,
                         prompt_len, temp, seed, state, slot)

        return jax.jit(step, donate_argnums=_donated(model))

    @program(SERVE_PREFILL)
    def step(params, pool_k, pool_v, table, tokens, start, prompt_len,
             temp, seed):
        return chunk(params, pool_k, pool_v, table, tokens, start,
                     prompt_len, temp, seed)

    return jax.jit(step, donate_argnums=(1, 2))


@jax.jit
@program(SERVE_FEED)
def _feed(host, sampled, first=None):
    """Each slot's pending token, from where it is. ``host`` (2, S)
    int32: row 0 the tokens the host has, row 1 where to look instead
    (1: ``sampled``, the (S,) samples of the decode step before, still
    unread; 2: ``first``, the first token of the final prefill chunk
    dispatched this step, a scalar; given only on such a step). It
    moves values and computes none, so the decode program sees the
    token it would have been handed by the host."""
    last, where = host
    if first is not None:
        last = jnp.where(where == 2, first, last)
    return jnp.where(where == 1, sampled, last)


@dataclasses.dataclass
class _Unread:
    """What one engine step sampled and left on the device: the first
    tokens of its final prefill chunks and the rows of its decode step,
    with the slot each belongs to (the ``SlotState`` itself: a slot
    retired and refilled meanwhile is another object) and the parameter
    version they were dispatched on."""

    version: int
    firsts: list = dataclasses.field(default_factory=list)  # (idx, slot, tok, lp)
    rows: dict = dataclasses.field(default_factory=dict)    # idx -> slot
    out: tuple = ()     # the decode step's (toks, lps, bad), on the device

    def __bool__(self) -> bool:
        return bool(self.firsts or self.rows)


class ServeEngine:
    """Continuous-batching serving over one dense single-device model:
    whatever offers what the decode core asks (models/decode.py), K/V
    in every layer or recurrent state in some.

    Knob defaults come from ``TrainConfig`` (``TPU_DDP_SERVE_SLOTS``,
    ``TPU_DDP_SERVE_BLOCK``, ``TPU_DDP_SERVE_PREFILL_CHUNK``,
    ``TPU_DDP_SERVE_CACHE_DTYPE`` — registered in tune/space.py under
    the "goodput" objective); explicit arguments win. ``num_blocks``
    defaults to a pool big enough that every slot can hold a
    ``max_seq_len`` sequence (no paging pressure); size it smaller to
    make admission control real.
    """

    def __init__(self, model, params, *, num_slots: int | None = None,
                 block_size: int | None = None,
                 prefill_chunk: int | None = None,
                 num_blocks: int | None = None,
                 cache_dtype: str | None = None,
                 mode: str = "continuous",
                 prefix_cache: bool | None = None,
                 queue_limit: int | None = None,
                 shed_ms: float | None = None,
                 tenant_classes: str | None = None,
                 spec_k: int | None = None,
                 spec_draft: str | None = None,
                 decode_quant: str | None = None,
                 kv_tiers: int | None = None,
                 kv_cold_dtype: str | None = None,
                 hbm_blocks: int | None = None,
                 cold_blocks: int | None = None,
                 cp_prefill: str | None = None,
                 mesh=None,
                 metrics: MetricsLogger | None = None,
                 config=None):
        check_decodable(model)
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
        if num_blocks is None or not kv_layers(model):
            # A pool without K/V layers holds nothing (a model whose every
            # layer keeps state): its blocks only mark which slots are
            # live, so its reservation never bounds the slots.
            num_blocks = max(num_blocks or 0,
                             self.num_slots * self.blocks_per_seq + 1)
        cache_dtype = (cache_dtype if cache_dtype is not None
                       else config.serve_cache_dtype)
        # Tiered KV (§27, TPU_DDP_KV_TIERS / TPU_DDP_KV_COLD_DTYPE):
        # tiers == 1 is the round-12 pool bit-for-bit; tiers > 1 bounds
        # HOT context by hbm_blocks while the logical pool (what the
        # scheduler admits against) stays num_blocks.
        self.kv_tiers = int(kv_tiers if kv_tiers is not None
                            else getattr(config, "kv_tiers", 1))
        self.kv_cold_dtype = str(
            kv_cold_dtype if kv_cold_dtype is not None
            else getattr(config, "kv_cold_dtype", "int8"))
        self.pool = PagedKVPool(model, num_blocks, self.block_size,
                                cache_dtype, tiers=self.kv_tiers,
                                cold_dtype=self.kv_cold_dtype,
                                hbm_blocks=hbm_blocks,
                                cold_blocks=cold_blocks)
        # The recurrent state of the layers that keep one, by slot
        # (no array at all for a model whose layers all keep K/V).
        self.state = StatePool(model, self.num_slots)
        # Tensor-parallel serving: params arrive pre-sharded over
        # ``mesh``'s model axis (parallel/tensor_parallel.py
        # shard_decode_params); the pool and every host-built input
        # ride replicated and GSPMD partitions the two jitted steps.
        self.mesh = mesh
        if mesh is not None:
            from tpu_ddp.parallel.mesh import replicated_sharding
            rep = replicated_sharding(mesh)
            self.pool.k = jax.device_put(self.pool.k, rep)
            self.pool.v = jax.device_put(self.pool.v, rep)
            if self.kv_tiers > 1:
                self.pool.cold_k = jax.device_put(self.pool.cold_k, rep)
                self.pool.cold_v = jax.device_put(self.pool.cold_v, rep)
                self.pool.cold_sk = jax.device_put(self.pool.cold_sk,
                                                   rep)
                self.pool.cold_sv = jax.device_put(self.pool.cold_sv,
                                                   rep)
        prefix_cache = (bool(prefix_cache) if prefix_cache is not None
                        else config.prefix_cache)
        self.prefix = None
        if prefix_cache:
            from tpu_ddp.fleet.prefix import PrefixIndex
            self.prefix = PrefixIndex(self.pool)
        # Tenant SLO classes (§25, TPU_DDP_TENANT_CLASSES): parsed
        # here, enforced by the scheduler's weighted-fair-queueing
        # admission and this engine's class-aware shedding. Empty =
        # single anonymous tenant, FIFO admission unchanged.
        tc = (tenant_classes if tenant_classes is not None
              else config.tenant_classes)
        self.tenants = parse_tenant_classes(tc) or None
        self.sched = Scheduler(self.pool, self.num_slots, mode,
                               prefix=self.prefix,
                               tenants=self.tenants)
        # Per-tenant ledger for the §25 accounting identity:
        # completed + cancelled + shed + in-flight == submitted, PER
        # tenant, at every step (completed includes quarantined —
        # the request terminated on this engine). drain() moves a
        # handle to another replica, so it debits ``submitted`` here;
        # the handle-level identity lives in loadgen/run_trace.
        self.tenant_counts: dict[str, dict[str, int]] = {}
        self.metrics = metrics if metrics is not None \
            else MetricsLogger(None)
        self.metrics.observe("serve_kv_pool_bytes", self.pool.nbytes)
        self.metrics.observe("serve_state_pool_bytes", self.state.nbytes)
        self._decode = _build_decode_step(model, self.block_size,
                                          self.blocks_per_seq)
        self._prefill = _build_prefill_step(model, self.block_size,
                                            self.blocks_per_seq)
        # the choice sparse_chunk makes as the prefill program is traced
        self._sparse_kernel = "sparse_attention" in model.mixers \
            and sparse_kernel_takes(model, self.prefill_chunk,
                                    self.blocks_per_seq * self.block_size,
                                    self.pool.dtype)
        # Long-context programs (§27). The tiered step twins replace
        # the decode/prefill programs only when tiers > 1 — at the
        # default they are never built and the round-12 programs run
        # untouched. Context-parallel prefill (TPU_DDP_CP_PREFILL)
        # swaps the prefill-chunk program for the sp-sharded one.
        self.cp_prefill = str(cp_prefill if cp_prefill is not None
                              else getattr(config, "cp_prefill", "off"))
        if self.cp_prefill not in ("off", "ring", "ulysses"):
            raise ValueError(
                f"cp_prefill={self.cp_prefill!r}: expected 'off', "
                "'ring' or 'ulysses' (TPU_DDP_CP_PREFILL)")
        self._tiered_decode = self._tiered_prefill = None
        if self.kv_tiers > 1:
            from tpu_ddp.serve.long_context import (
                build_tiered_decode_step, build_tiered_prefill_step)
            self._tiered_decode = build_tiered_decode_step(
                model, self.block_size, self.blocks_per_seq)
            self._tiered_prefill = build_tiered_prefill_step(
                model, self.block_size, self.blocks_per_seq)
        if self.cp_prefill != "off":
            if self.kv_tiers > 1:
                raise ValueError(
                    "cp_prefill requires the single-tier pool "
                    "(TPU_DDP_KV_TIERS=1): the sharded chunk step "
                    "scatters through the logical table directly")
            if mesh is None or "sp" not in mesh.shape \
                    or mesh.shape["sp"] < 2:
                raise ValueError(
                    "cp_prefill needs a mesh with an 'sp' axis of "
                    "extent >= 2 (TPU_DDP_CP_PREFILL)")
            sp = mesh.shape["sp"]
            if self.prefill_chunk % sp:
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must divide "
                    f"evenly over sp={sp} ranks (TPU_DDP_CP_PREFILL)")
            from tpu_ddp.serve.long_context import build_cp_prefill_step
            self._prefill = build_cp_prefill_step(
                model, self.block_size, self.blocks_per_seq, mesh, sp,
                self.cp_prefill)
        # Speculative decoding + quantized decode compute (§26,
        # TPU_DDP_SPEC_K / TPU_DDP_SPEC_DRAFT / TPU_DDP_DECODE_QUANT):
        # same knob convention as above — explicit arguments win over
        # config, which already folded in the env surface.
        self.spec_k = int(spec_k if spec_k is not None
                          else getattr(config, "spec_k", 0))
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self.spec_draft = str(
            spec_draft if spec_draft is not None
            else getattr(config, "spec_draft", "self-1"))
        kind, j = parse_spec_draft(self.spec_draft)
        if kind == "self" and j > model.num_layers:
            raise ValueError(
                f"spec_draft={self.spec_draft!r}: draft depth {j} "
                f"exceeds the model's {model.num_layers} blocks")
        self._spec_kind, self._spec_j = kind, j
        self.decode_quant = str(
            decode_quant if decode_quant is not None
            else getattr(config, "decode_quant", "none"))
        if self.decode_quant not in ("none", "int8"):
            raise ValueError(
                f"decode_quant={self.decode_quant!r}: expected 'none'"
                " or 'int8' (TPU_DDP_DECODE_QUANT)")
        check_state_servable(
            model, prefix_cache=prefix_cache, kv_tiers=self.kv_tiers > 1,
            spec_k=self.spec_k > 0, cp_prefill=self.cp_prefill != "off",
            decode_quant=self.decode_quant != "none",
            mesh=mesh is not None)
        if model.moe_experts and (self.decode_quant == "int8"
                                  or kind == "quant"):
            # The routed MoE layer contracts stacked expert weights in
            # raw einsums (tpu_ddp/parallel/moe.py), not qdot — int8
            # QuantizedWeight leaves would not trace. Refuse loudly
            # rather than serve a silently-dequantized tree.
            raise ValueError(
                "decode_quant='int8' (and the 'quant' draft family) "
                "do not support MoE models yet: the routed expert "
                "einsums bypass ops/quant.qdot; serve MoE with "
                "decode_quant='none'")
        self._refresh_quant()
        self._spec = None
        if self.spec_k > 0:
            # The fused draft+verify program.
            self._spec = build_spec_step(
                model, self.block_size, self.blocks_per_seq,
                self.spec_k, j if kind == "self" else model.num_layers)
        # Engine-level speculation ledger (spec_stats(); per-request
        # counts live on the Request handle).
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        self._rid = itertools.count()
        self.config = config
        # SLO-aware load shedding (docs/DESIGN.md §23): queue_limit
        # bounds the admission queue (0 = unbounded, the default);
        # shed_ms drops queued requests whose wait already blew the
        # deadline (0 = off). Both shed honestly: the request handle
        # comes back done+shed, and loadgen counts it against goodput.
        self.queue_limit = int(queue_limit if queue_limit is not None
                               else config.serve_queue_limit)
        self.shed_ms = float(shed_ms if shed_ms is not None
                             else config.serve_shed_ms)
        if self.queue_limit < 0:
            raise ValueError("queue_limit must be >= 0")
        if self.shed_ms < 0:
            raise ValueError("shed_ms must be >= 0")
        self._step_n = 0
        # The step before's samples, dispatched and not yet read back
        # (None: the engine is at rest), and the newest decode step's
        # sampled tokens on the device, which the next step feeds from.
        self._unread: _Unread | None = None
        self._sampled = jnp.zeros(self.num_slots, jnp.int32)
        # seconds this step spent blocked in fetch; when the next tally
        self._fetch_s = 0.0
        self._tally_due = 0.0
        # Weight streaming (tpu_ddp/publish/): the served version id
        # and the subscriber that advances it. ``swap_params`` is the
        # ONLY mutation path for ``self.params`` after construction —
        # both jitted step programs take params as an ARGUMENT (never
        # closed over, never donated), so a shape/dtype-identical swap
        # reuses the compiled programs by construction.
        self.param_version = 0
        self.subscriber = None
        self.chaos = None
        from tpu_ddp.fleet.resilience import (
            ServeFaultInjector, serve_chaos_active)
        if serve_chaos_active():
            self.chaos = ServeFaultInjector.from_env()
        # TPU_DDP_AUDIT=warn|error: static donation/precision audit of
        # the two step programs before the engine takes traffic
        # (tpu_ddp/analysis/gate.py; shapes are fully static here).
        if getattr(config, "audit", "off") != "off":
            from tpu_ddp.analysis.gate import maybe_audit_serve_engine
            maybe_audit_serve_engine(self)

    def _refresh_quant(self) -> None:
        """(Re)derive the decode-path parameter tree from the fp
        master ``self.params`` — at construction and after every
        :meth:`swap_params` flip, which is how the publish Subscriber
        re-quantizes on hot-swap without knowing quantization exists.

        ``self._decode_params`` feeds EVERY compiled step program
        (decode, prefill, fused speculative verify): the fp tree under
        ``decode_quant == "none"``, the per-channel int8 tree
        (ops/quant.py quantize_params) under ``"int8"``. The two trees
        have different treedefs (QuantizedWeight leaves), so jit keys
        them to distinct compiled programs automatically — no engine
        dispatch logic. ``self.params`` stays the fp master.
        ``self._draft_params`` is the fused draft's tree: the decode
        tree for a "self-<j>" early exit (the draft IS the target's
        first j blocks), the int8 tree for a "quant" draft (shared
        with ``_decode_params`` when the target is itself int8)."""
        qp = None
        if self.decode_quant == "int8" or self._spec_kind == "quant":
            from tpu_ddp.ops.quant import quantize_params
            qp = pin_committed(quantize_params(self.model, self.params))
        self._decode_params = (qp if self.decode_quant == "int8"
                               else self.params)
        self._draft_params = (qp if self._spec_kind == "quant"
                              else self._decode_params)

    def lower_decode_step(self):
        """``jit.lower`` the whole-bank decode step at the engine's
        static shapes — the HLO-inspection surface the graph audit
        (tpu_ddp/analysis/) fingerprints and donation-checks."""
        S, BPS = self.num_slots, self.blocks_per_seq
        sds = jax.ShapeDtypeStruct
        return self._decode.lower(
            self._decode_params, self.pool.k, self.pool.v,
            *self._state_args(),
            sds((S, BPS), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.float32),
            sds((S,), jnp.int32))

    def lower_prefill_step(self):
        """``jit.lower`` the one-slot prefill-chunk step (same audit
        surface as :meth:`lower_decode_step`)."""
        sds = jax.ShapeDtypeStruct
        return self._prefill.lower(
            self._decode_params, self.pool.k, self.pool.v,
            *self._state_args(),
            sds((self.blocks_per_seq,), jnp.int32),
            sds((1, self.prefill_chunk), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((), jnp.float32), sds((), jnp.int32),
            *((sds((), jnp.int32),) if self._state_args() else ()))

    def _state_args(self) -> tuple:
        """The state pool's arrays and the index-key pages as the step
        programs take them: one more donated argument after the K/V
        pool's, or none for a model that keeps neither."""
        arrays = dict(self.state.arrays)
        if self.pool.index is not None:
            arrays["index"] = self.pool.index
        return (arrays,) if arrays else ()

    def _commit_state(self, arrays=None) -> None:
        """Store what a step program returned for :meth:`_state_args`."""
        if arrays is not None and self.pool.index is not None:
            arrays = dict(arrays)
            self.pool.index = arrays.pop("index")
        self.state.commit(arrays or None)

    def lower_spec_step(self):
        """``jit.lower`` the fused speculative step (same audit
        surface). Raises at ``spec_k == 0`` — no speculative program
        exists there."""
        if self._spec is None:
            raise ValueError(
                "no fused speculative program: spec_k == 0")
        S, BPS = self.num_slots, self.blocks_per_seq
        sds = jax.ShapeDtypeStruct
        return self._spec.lower(
            self._decode_params, self._draft_params,
            self.pool.k, self.pool.v,
            sds((S, BPS), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.float32),
            sds((S,), jnp.int32), sds((S,), jnp.int32))

    def lower_tiered_decode_step(self):
        """``jit.lower`` the tiered whole-bank decode step (§27 audit
        surface). Raises at ``kv_tiers == 1`` — no tiered programs
        exist there by construction."""
        if self._tiered_decode is None:
            raise ValueError("no tiered decode program: kv_tiers == 1")
        S, BPS = self.num_slots, self.blocks_per_seq
        sds = jax.ShapeDtypeStruct
        return self._tiered_decode.lower(
            self._decode_params, self.pool.k, self.pool.v,
            self.pool.cold_k, self.pool.cold_v,
            self.pool.cold_sk, self.pool.cold_sv,
            sds((S, BPS), jnp.int32), sds((S, BPS), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.float32), sds((S,), jnp.int32))

    def lower_tiered_prefill_step(self):
        """``jit.lower`` the tiered one-slot prefill-chunk step."""
        if self._tiered_prefill is None:
            raise ValueError("no tiered prefill program: kv_tiers == 1")
        BPS = self.blocks_per_seq
        sds = jax.ShapeDtypeStruct
        return self._tiered_prefill.lower(
            self._decode_params, self.pool.k, self.pool.v,
            self.pool.cold_k, self.pool.cold_v,
            self.pool.cold_sk, self.pool.cold_sv,
            sds((BPS,), jnp.int32), sds((BPS,), jnp.int32),
            sds((1, self.prefill_chunk), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((), jnp.float32), sds((), jnp.int32))

    @classmethod
    def from_checkpoint(cls, model, directory: str,
                        step: int | None = None, *,
                        param_budget_bytes: int | None = None,
                        shard_devices=None, **kwargs):
        """Load a trained checkpoint (any strategy — the artifact is
        canonical) into a fresh engine: the train→serve round trip.

        When the dense params exceed ``param_budget_bytes`` (one
        chip's budget) — or ``shard_devices`` is passed explicitly —
        the engine serves tensor-parallel: params shard over the
        Megatron head/d_ff axes (parallel/tensor_parallel.py) across
        the given devices and both jitted steps run under GSPMD.
        Below budget the round-12 single-chip path is unchanged."""
        params = dense_params_from_checkpoint(model, directory, step)
        if shard_devices is None and param_budget_bytes is not None:
            nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
            if nbytes > param_budget_bytes:
                shard_devices = jax.devices()
        if shard_devices is not None:
            from tpu_ddp.parallel.tensor_parallel import (
                shard_decode_params,
            )
            params, mesh = shard_decode_params(model, params,
                                               shard_devices)
            return cls(model, params, mesh=mesh, **kwargs)
        return cls(model, params, **kwargs)

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
        self.metrics.inc("serve_submitted")
        self._tc(req.tenant)["submitted"] += 1
        if self.queue_limit and len(self.sched.queue) >= self.queue_limit:
            # Bounded admission queue: shed at the door rather than
            # queueing work that can only finish past its deadline.
            # With tenant classes, shed LOWEST CLASS FIRST: a queue
            # full of bronze must not bounce an arriving gold — evict
            # the lowest-weight queued request (newest among ties)
            # instead, when the newcomer strictly outranks it.
            victim = req
            if self.tenants:
                lowest = min(
                    self.sched.queue,
                    key=lambda r: (self._weight(tenant_of(r)), -r.rid),
                    default=None)
                if lowest is not None \
                        and self._weight(tenant_of(lowest)) \
                        < self._weight(req.tenant):
                    self.sched._remove_queued(lowest)
                    self._shed(lowest)
                    victim = None
            if victim is not None:
                self._shed(victim)
                return req
        self.sched.enqueue(req)
        return req

    def _tc(self, tenant: str) -> dict[str, int]:
        return self.tenant_counts.setdefault(
            tenant, {"submitted": 0, "completed": 0, "cancelled": 0,
                     "shed": 0, "quarantined": 0})

    def _weight(self, tenant: str) -> int:
        cls = self.tenants.get(tenant) if self.tenants else None
        return cls.weight if cls is not None else 1

    def _shed(self, req: Request) -> None:
        req.shed = True
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.inc("serve_shed")
        self._tc(tenant_of(req))["shed"] += 1

    def _shed_expired(self) -> None:
        """Deadline-based shedding: a request still queued (no block
        held, no token emitted) past its deadline is dropped — serving
        it would only burn capacity on an already-missed SLO. The
        deadline is the tighter of the global ``shed_ms`` and the
        request's tenant-class ``deadline_ms`` (either 0 = off)."""
        if not self.shed_ms and not self.tenants:
            return
        now = time.perf_counter()
        expired = []
        for r in self.sched.queue:
            limits = [self.shed_ms]
            if self.tenants:
                cls = self.tenants.get(tenant_of(r))
                limits.append(cls.deadline_ms if cls is not None else 0.0)
            limits = [m for m in limits if m > 0]
            if limits and (now - r.submitted_at) * 1e3 > min(limits):
                expired.append(r)
        for r in expired:
            self.sched._remove_queued(r)
            self._shed(r)

    def cancel(self, req: Request) -> bool:
        """Drop a queued or live request; frees its blocks. Returns
        whether there was anything to cancel."""
        if req.done:
            return False
        if req in self.sched.queue:
            self.sched.queue.remove(req)
        else:
            # A live request may have a row in the step in flight: its
            # tokens are handed out first, and may be its last.
            self._rest()
            if req.done:
                return False
            for i, s in enumerate(self.sched.slots):
                if s is not None and s.request is req:
                    self.sched.retire(i)
                    break
            else:
                return False
        req.cancelled = True
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.inc("serve_cancelled")
        self._tc(tenant_of(req))["cancelled"] += 1
        return True

    # ---- the iteration -------------------------------------------------

    def step(self) -> bool:
        """One engine iteration: admit, prefill, one whole-batch
        decode step. Returns whether any work ran; ``False`` also
        means that nothing is in flight.

        The step has two bodies. At ``spec_k == 0`` it dispatches its
        prefill chunk and its decode program first and then hands out
        the tokens of the step BEFORE it (:meth:`_harvest`), so a token
        reaches its request one ``step()`` after the step that sampled
        it, and the host's work runs beside the device's
        (docs/DESIGN.md §19). At ``spec_k > 0`` it comes to rest after
        its chunks and runs the fused draft+verify step
        (:meth:`_run_spec_step`), which reads its samples back at once.

        Prefill budget: at most one chunk per step at ``spec_k == 0``
        (the latency-smoothing default), ``spec_k + 1`` chunks when
        speculating — a speculative step retires up to ``spec_k + 1``
        tokens per slot, so single-chunk refill would starve the bank
        (slots empty faster than they refill) and the window would run
        at a fraction of its width. Matching the budgets keeps bank
        occupancy at its k=0 level."""
        t0 = time.perf_counter()
        if t0 >= self._tally_due:
            self._tally_due = t0 + TALLY_S
            mark("tpu_ddp.serve.tally", self._tally)
        self._step_n += 1
        self._fetch_s = 0.0
        with burst(self._step_n), span("tpu_ddp.serve.step"):
            did = self._step()
        wall_ms = (time.perf_counter() - t0) * 1e3
        fetch_ms = self._fetch_s * 1e3
        self.metrics.observe("serve_host_busy_ms", wall_ms - fetch_ms)
        self.metrics.observe("serve_fetch_wait_ms", fetch_ms)
        self.metrics.observe("serve_kv_blocks_in_use",
                             self.pool.total_usable - self.pool.free_count)
        return did

    def _tally(self) -> dict:
        """The counts of a ``serve.tally``: running totals since the
        engine was built, out of its ``MetricsLogger``."""
        c, g = self.metrics.counters, self.metrics.gauges
        empty = {"count": 0, "total": 0.0}

        def gauge(name):
            return g.get(name, empty)

        return {
            "steps": gauge("serve_host_busy_ms")["count"],
            "decode_steps": gauge("serve_decode_rows")["count"],
            "decode_ahead": c.get("serve_decode_ahead", 0),
            "dry_steps": c.get("serve_decode_dry", 0),
            "decode_rows": gauge("serve_decode_rows")["total"],
            "context_tokens": gauge("serve_decode_context_tokens")["total"],
            "prefill_chunks": gauge("serve_prefill_tokens")["count"],
            "prefill_tokens": gauge("serve_prefill_tokens")["total"],
            "prefill_sparse_kernel_chunks":
            gauge("serve_prefill_sparse_kernel_chunks")["total"],
            "prefill_state_carried":
            gauge("serve_prefill_state_carried")["total"],
            "kv_blocks_in_use": gauge("serve_kv_blocks_in_use")["total"],
            "kv_blocks_usable": self.pool.total_usable,
            "host_busy_ms": gauge("serve_host_busy_ms")["total"],
            "fetch_wait_ms": gauge("serve_fetch_wait_ms")["total"],
            "queue_depth": gauge("serve_queue_depth")["total"],
            "selected_tokens": gauge("serve_decode_selected_tokens")["total"],
            "index_scored_tokens":
            gauge("serve_decode_index_tokens")["total"],
        }

    def _step(self) -> bool:
        with span("tpu_ddp.serve.schedule"):
            if self.chaos is not None:
                # May raise ReplicaCrashError — BEFORE any state
                # mutation, so a router-harvested engine is always
                # consistent.
                self.chaos.replica_step(self._step_n)
                # The non-finite drill corrupts pool pages from the
                # host: it finds the engine at rest.
                if self.chaos.poison_due(self._step_n):
                    self._rest()
            if self.subscriber is not None:
                # Weight streaming: stage at most one delta bucket, flip
                # the version when an update completes — BETWEEN steps,
                # so a flip is atomic at token granularity (the token
                # this step samples is entirely on the flipped-to
                # version; swap_params brings the engine to rest first).
                self.subscriber.on_engine_step()
            self._shed_expired()
            admitted = self.sched.admit()
            now = time.perf_counter()
            for i in admitted:
                self.metrics.inc("serve_admitted")
                s = self.sched.slots[i]
                # A marker, not a region: a span's counts are fixed
                # when it opens, so what was admitted hangs under
                # ``schedule`` as its children.
                with span("tpu_ddp.serve.admit", rid=s.request.rid,
                          waited_ms=(now - s.request.submitted_at) * 1e3,
                          prompt_tokens=int(s.request.prompt.size),
                          cached_tokens=s.prefill_done):
                    pass
            pi = self.sched.prefill_slot()
        # ``before``: what the step before left unread; ``mine``: what
        # this step leaves. Nothing below reads ``before`` back until
        # this step's programs are dispatched.
        before = self._unread
        mine = _Unread(self.param_version)
        did = before is not None
        # ahead: the decode step before this one is still unread (a final
        # chunk's first token alone does not count); dry: and the device
        # had finished it before this step dispatched anything
        # (is_ready does not wait)
        ahead = int(did and bool(before.rows))
        dry = int(ahead and before.out[0].is_ready())

        budget = self.spec_k + 1 if self.spec_k > 0 else 1
        for chunk in range(budget):
            if chunk:
                with span("tpu_ddp.serve.schedule"):
                    pi = self.sched.prefill_slot()
            if pi is None:
                break
            did = True
            self._run_prefill_chunk(pi, mine)

        if self.spec_k > 0:
            # The speculative step is synchronous and reads
            # ``pending_token`` on the host: the first tokens of this
            # step's chunks are handed out before it.
            self._unread = mine or None
            self._rest()
        with span("tpu_ddp.serve.schedule"):
            dslots = self.sched.decode_slots()
        if dslots:
            did = True
            if self.spec_k == 0:
                self.metrics.inc("serve_decode_ahead" if ahead
                                 else "serve_decode_at_rest")
            context = sum(self.sched.slots[i].length
                          + self.sched.slots[i].ahead for i in dslots)
            self.metrics.observe("serve_decode_rows", len(dslots))
            self.metrics.observe("serve_decode_context_tokens", context)
            selected = 0
            if self.pool.index is not None:
                # each row reads its positions 0..length (the new one
                # included), of which attention keeps at most index_topk
                top = self.model.index_topk
                selected = sum(min(self.sched.slots[i].length
                                   + self.sched.slots[i].ahead + 1, top)
                               for i in dslots)
                self.metrics.observe("serve_decode_selected_tokens",
                                     selected)
                self.metrics.observe("serve_decode_index_tokens",
                                     context + len(dslots))
            if dry:
                self.metrics.inc("serve_decode_dry")
            with span("tpu_ddp.serve.decode", context_tokens=context,
                      ahead=ahead, **({"dry": dry} if ahead else {}),
                      state_slots=len(dslots) if self.state.arrays
                      else 0, selected_tokens=selected):
                if self.spec_k > 0:
                    self._run_spec_step(dslots)
                else:
                    self._run_decode_step(dslots, mine)
                    self._turn_over(before, mine)
        elif self.spec_k == 0:
            self._turn_over(before, mine)

        self.metrics.observe("serve_queue_depth",
                             len(self.sched.queue))
        self.metrics.observe("serve_slot_occupancy",
                             self.sched.live / self.num_slots)
        return did or bool(admitted)

    def run(self, max_steps: int | None = None) -> int:
        """Step until idle (queue drained, all slots free) or
        ``max_steps``. Returns the number of steps taken."""
        n = 0
        while max_steps is None or n < max_steps:
            if not self.step():
                break
            n += 1
        self._rest()    # max_steps can stop it with a step in flight
        return n

    def swap_params(self, params, version: int) -> None:
        """Atomically flip the served weights to ``params`` at
        ``version`` (tpu_ddp/publish/subscriber.py calls this between
        steps). The tree must match the current layout bitwise in
        shapes/dtypes — then both compiled step programs are reused
        as-is (params are a jit *argument*, pinned by the no-retrace
        test), and the very next decode step samples on ``version``.
        The step in flight is read back first: its tokens carry the
        version they were dispatched on."""
        self._rest()
        self.params = params
        self.param_version = int(version)
        # Quantized serving re-derives the int8 decode tree from the
        # new fp master — the subscriber's hot-swap re-quantizes by
        # construction, with no publish-side knowledge of the knob.
        self._refresh_quant()

    # ---- router hooks --------------------------------------------------

    def outstanding(self) -> int:
        """Tokens of work still owed (queued + live) — the router's
        least-loaded load estimate."""
        w = 0
        for r in self.sched.queue:
            w += len(r.prompt) + r.max_new_tokens
        for s in self.sched.slots:
            if s is not None:
                w += (len(s.request.prompt) - s.prefill_done) \
                    + (s.request.max_new_tokens - s.generated)
        return w

    def prefix_cached_len(self, prompt, tenant: str = "default") -> int:
        """Prompt tokens this engine's prefix cache already holds
        WITHIN the tenant's namespace — the router's prefix-affinity
        signal (0 without a cache)."""
        if self.prefix is None:
            return 0
        return self.prefix.cached_len(
            np.asarray(prompt, np.int32).reshape(-1), ns=tenant)

    def accounting_ok(self) -> bool:
        return self.sched.accounting_ok()

    def outstanding_by_tenant(self) -> dict[str, int]:
        """``outstanding()`` partitioned by tenant — the autoscaler's
        tenant-scoped backlog signal. Computed live from the queue and
        slots (never a cached counter), so cancel/shed/drain can't
        leave ghost load behind."""
        out: dict[str, int] = {}
        for r in self.sched.queue:
            t = tenant_of(r)
            out[t] = out.get(t, 0) + len(r.prompt) + r.max_new_tokens
        for s in self.sched.slots:
            if s is not None:
                t = tenant_of(s.request)
                out[t] = out.get(t, 0) \
                    + (len(s.request.prompt) - s.prefill_done) \
                    + (s.request.max_new_tokens - s.generated)
        return out

    def _tenant_in_flight(self, tenant: str) -> int:
        n = sum(tenant_of(r) == tenant for r in self.sched.queue)
        n += sum(s is not None and tenant_of(s.request) == tenant
                 for s in self.sched.slots)
        return n

    def tenant_accounting_ok(self) -> bool:
        """The §25 identity, per tenant: completed + cancelled + shed
        + in-flight == submitted on THIS engine, for every tenant ever
        seen."""
        for t, c in self.tenant_counts.items():
            if c["completed"] + c["cancelled"] + c["shed"] \
                    + self._tenant_in_flight(t) != c["submitted"]:
                return False
        return True

    def tenant_stats(self) -> dict[str, dict]:
        """Per-tenant ledger + live load, for stats()/debugging."""
        live = self.outstanding_by_tenant()
        return {t: dict(c, outstanding=live.get(t, 0))
                for t, c in sorted(self.tenant_counts.items())}

    # ---- internals -----------------------------------------------------

    def _table_for(self, slot) -> np.ndarray:
        t = np.zeros(self.blocks_per_seq, np.int32)
        t[:len(slot.blocks)] = slot.blocks
        return t

    def _run_prefill_chunk(self, pi: int, mine: _Unread) -> None:
        s = self.sched.slots[pi]
        start = s.prefill_done
        end = min(start + self.prefill_chunk, int(s.request.prompt.size))
        self.metrics.observe("serve_prefill_tokens", end - start)
        # 1 where the chunk starts from the state the slot carries
        carried = int(bool(self.state.arrays) and start > 0)
        if self.state.arrays:
            self.metrics.observe("serve_prefill_state_carried", carried)
        if self.pool.index is not None:
            self.metrics.observe("serve_prefill_sparse_kernel_chunks",
                                 int(self._sparse_kernel))
        # a sparse-attention model's indexer scores each query against
        # its positions 0..p: sum of p + 1 over the chunk's prompt tokens
        scored = ((end * (end + 1) - start * (start + 1)) // 2
                  if self.pool.index is not None else 0)
        with span("tpu_ddp.serve.prefill", rid=s.request.rid,
                  tokens=end - start, start=start,
                  final=int(end >= s.request.prompt.size),
                  state_reset=int(bool(self.state.arrays)
                                  and start == 0),
                  state_carried=carried,
                  index_scored_tokens=scored,
                  sparse_kernel=int(self._sparse_kernel)):
            self._prefill_chunk(pi, s, start, mine)

    def _prefill_chunk(self, pi: int, s, start: int,
                       mine: _Unread) -> None:
        req = s.request
        C = self.prefill_chunk
        chunk = np.zeros((1, C), np.int32)
        piece = req.prompt[start:start + C]
        chunk[0, :piece.size] = piece
        if self.pool.tiers > 1:
            # This chunk's target blocks must be hot (the scatter
            # addresses hot slots); earlier chunks' pages may have
            # gone cold under hot pressure and are read through the
            # dequant — which is exactly how a prompt larger than the
            # hot tier prefills at all.
            lastpos = min(start + C, int(req.prompt.size)) - 1
            targets = s.blocks[start // self.block_size:
                               lastpos // self.block_size + 1]
            self.pool.ensure_device(s.blocks)
            self.pool.ensure_hot(targets, keep=s.blocks)
            ht, ct = self.pool.slot_tables(s.blocks,
                                           self.blocks_per_seq)
            k, v, tok, lp = self._tiered_prefill(
                self._decode_params, self.pool.k, self.pool.v,
                self.pool.cold_k, self.pool.cold_v,
                self.pool.cold_sk, self.pool.cold_sv,
                jnp.asarray(ht), jnp.asarray(ct), jnp.asarray(chunk),
                jnp.int32(start), jnp.int32(req.prompt.size),
                jnp.float32(req.temperature), jnp.int32(req.seed))
        else:
            # a model with recurrent state: its arrays go in after the
            # pool's and come back last, and the program is told the slot
            state = self._state_args()
            k, v, tok, lp, *state = self._prefill(
                self._decode_params, self.pool.k, self.pool.v, *state,
                jnp.asarray(self._table_for(s)), jnp.asarray(chunk),
                jnp.int32(start), jnp.int32(req.prompt.size),
                jnp.float32(req.temperature), jnp.int32(req.seed),
                *((jnp.int32(pi),) if state else ()))
            self._commit_state(*state)
        self.pool.commit(k, v)
        s.prefill_done = min(start + C, int(req.prompt.size))
        s.length = s.prefill_done
        if s.prefill_done >= req.prompt.size:
            # Register at dispatch, so before the first token is
            # emitted: _emit may retire the slot (max_new_tokens == 1),
            # and the index must take its holder refs while the blocks
            # are still live.
            if self.prefix is not None:
                self.prefix.register(req.prompt, s.blocks,
                                     ns=tenant_of(req))
            s.phase = "decode"
            # The first token stays on the device: this step's decode
            # program is fed from there, the host reads it a step on.
            s.first_unread = True
            mine.firsts.append((pi, s, tok, lp))

    def _maybe_poison(self, dslots: list[int]) -> None:
        """The ``nonfinite-logits`` chaos drill: corrupt ONE live
        request's private KV pages with NaN host-side. The poison
        reaches the victim's logits through its own gathered cache
        view only (disjoint block tables), so the in-graph ``bad``
        flag must isolate exactly that slot. The step it is due on
        began by bringing the engine to rest."""
        if self.chaos is None or not dslots \
                or not self.chaos.poison_fires(self._step_n):
            return
        s = self.sched.slots[dslots[0]]
        # The LAST block is always private (lazily allocated, or the
        # CoW copy a prefix hit made) — never poison a block a prefix
        # cache shares with innocent requests.
        blk = s.blocks[-1]
        if self.pool.tiers > 1:
            # Poison the HOT copy; a later demote carries the NaN into
            # the cold page (NaN survives both cold codecs), so the
            # drill holds wherever the page ends up.
            self.pool.ensure_hot([blk])
            blk = self.pool.hot_slot(blk)
        self.pool.v = self.pool.v.at[:, blk].set(jnp.nan)

    def _run_decode_step(self, dslots: list[int], mine: _Unread) -> None:
        """Dispatch one token for every slot of ``dslots`` and leave
        the samples on the device, recorded in ``mine``. Everything
        here is built from what the host knows a step ahead: a slot
        whose last decode row is still unread (``ahead``) writes at
        ``length + 1`` and is fed that row's sample by ``serve_feed``;
        one whose final prefill chunk went out this step is fed the
        chunk's first token the same way."""
        S, BPS = self.num_slots, self.blocks_per_seq
        tiered = self.pool.tiers > 1
        slots = [self.sched.slots[i] for i in dslots]
        with span("tpu_ddp.serve.decode.tables"):
            tables = np.zeros((S, BPS), np.int32)
            lengths = np.zeros(S, np.int32)
            # row 0: the pending tokens the host has; row 1: where the
            # others are (_feed)
            last = np.zeros((2, S), np.int32)
            temps = np.zeros(S, np.float32)
            seeds = np.zeros(S, np.int32)
            if tiered:
                # Residency before tables: poison first (its promote
                # may shuffle tiers), then the whole read set on device,
                # then every slot's write-frontier block hot — one
                # batched call so no frontier evicts another.
                self._maybe_poison(dslots)
                allblocks, frontiers = [], []
                for i, s in zip(dslots, slots):
                    self.sched.ensure_blocks(i, 1 + s.ahead)
                    allblocks.extend(s.blocks)
                    frontiers.append(
                        s.blocks[(s.length + s.ahead) // self.block_size])
                self.pool.ensure_device(allblocks)
                self.pool.ensure_hot(frontiers, keep=allblocks)
                cold_tables = np.zeros((S, BPS), np.int32)
            for i, s in zip(dslots, slots):
                if tiered:
                    tables[i], cold_tables[i] = self.pool.slot_tables(
                        s.blocks, BPS)
                else:
                    # the block of the position this row writes: one
                    # past ``length`` where the row before is unread
                    self.sched.ensure_blocks(i, 1 + s.ahead)
                    tables[i] = self._table_for(s)
                lengths[i] = s.length + s.ahead
                if s.ahead:
                    last[1, i] = 1
                elif s.first_unread:
                    last[1, i] = 2
                else:
                    last[0, i] = s.pending_token
                temps[i] = s.request.temperature
                seeds[i] = s.request.seed
            if not tiered:
                self._maybe_poison(dslots)
        with span("tpu_ddp.serve.decode.dispatch"):
            first = ()
            if 2 in last[1]:
                # this step's one final chunk (the speculative step,
                # with its larger chunk budget, does not come here)
                (_, _, tok, _), = mine.firsts
                first = (tok,)
            d_last = _feed(jnp.asarray(last), self._sampled, *first)
            if tiered:
                k, v, toks, lps, bad = self._tiered_decode(
                    self._decode_params, self.pool.k, self.pool.v,
                    self.pool.cold_k, self.pool.cold_v,
                    self.pool.cold_sk, self.pool.cold_sv,
                    jnp.asarray(tables), jnp.asarray(cold_tables),
                    jnp.asarray(lengths), d_last,
                    jnp.asarray(temps), jnp.asarray(seeds))
            else:
                k, v, toks, lps, bad, *state = self._decode(
                    self._decode_params, self.pool.k, self.pool.v,
                    *self._state_args(),
                    jnp.asarray(tables), jnp.asarray(lengths),
                    d_last, jnp.asarray(temps),
                    jnp.asarray(seeds))
                self._commit_state(*state)
            self.pool.commit(k, v)
            mine.rows = dict(zip(dslots, slots))
            mine.out = (toks, lps, bad)
            for out in mine.out:
                out.copy_to_host_async()
            self._sampled = toks
            for s in slots:
                s.ahead += 1

    def _rest(self) -> None:
        """Bring the engine to rest: read back and hand out what the
        last step left on the device. Whatever touches slot or pool
        state between steps calls this first (``cancel``, ``drain``,
        ``swap_params``, the non-finite drill, the speculative step);
        ``run()`` ends with it."""
        unread, self._unread = self._unread, None
        if unread is not None:
            self._harvest(unread)

    def _turn_over(self, before: _Unread | None, mine: _Unread) -> None:
        """The end of a plain step: what it dispatched is now the
        unread work, and the step before's is read back."""
        self._unread = mine or None
        if before is not None:
            self._harvest(before)

    @contextlib.contextmanager
    def _fetching(self):
        """A blocking read of samples: ``serve.decode.fetch``, its
        seconds added to the step's wait."""
        t = time.perf_counter()
        try:
            with span("tpu_ddp.serve.decode.fetch"):
                yield
        finally:
            self._fetch_s += time.perf_counter() - t

    def _harvest(self, unread: _Unread) -> None:
        """Read one step's samples back and hand them out in the order
        the synchronous engine did: the first tokens of its final
        chunks, then its decode rows. Inside a step this comes after
        the step's own dispatches, so each wait is for a program that
        is finishing while the next is queued behind it; a first token
        is handed out when its chunk is done and does not wait for the
        decode program behind the chunk.

        A row whose slot is gone was dispatched past an end the host
        could not see (EOS or non-finite logits on the token before):
        it wrote at an in-budget position of that slot's own blocks,
        which were freed or scrubbed in stream order after it, and its
        sample is dropped (DESIGN.md §19)."""
        if unread.firsts:
            with self._fetching():
                firsts = [(int(tok), float(lp))
                          for _, _, tok, lp in unread.firsts]
            with span("tpu_ddp.serve.decode.emit"):
                for (i, s, _, _), (tok, lp) in zip(unread.firsts, firsts):
                    s.first_unread = False
                    self._emit(i, tok, lp, unread.version)
        if not unread.rows:
            return
        with self._fetching():
            toks, lps, bad = map(np.asarray, unread.out)
        with span("tpu_ddp.serve.decode.emit"):
            for i, s in unread.rows.items():
                if self.sched.slots[i] is not s:
                    continue
                s.ahead -= 1
                if bad[i]:
                    self._quarantine(i)
                    continue
                s.length += 1
                self._emit(i, int(toks[i]), float(lps[i]), unread.version)

    def _run_spec_step(self, dslots: list[int]) -> None:
        """The fused draft+verify speculative step (spec_draft
        "self-<j>" / "quant"): ONE dispatch drafts k proposals per
        slot and verifies all k+1 columns with the target
        (speculative.build_spec_step), then the host emits the longest
        prefix of TARGET samples whose inputs the draft guessed right
        (accept_length — never a draft token). Rejected tail blocks go
        back to the pool via the scheduler's ``trim_blocks`` rollback,
        so ``accounting_ok()`` holds between steps."""
        with span("tpu_ddp.serve.decode.tables"):
            S, BPS = self.num_slots, self.blocks_per_seq
            tables = np.zeros((S, BPS), np.int32)
            lengths = np.zeros(S, np.int32)
            last = np.zeros(S, np.int32)
            temps = np.zeros(S, np.float32)
            seeds = np.zeros(S, np.int32)
            limits = np.zeros(S, np.int32)
            tiered = self.pool.tiers > 1
            if tiered:
                self._maybe_poison(dslots)
            for i in dslots:
                self.sched.ensure_blocks(i, self.spec_k + 1)
            if tiered:
                # All-hot translation: the fused draft+verify program
                # addresses ONE buffer, so every block it touches promotes
                # first and the table carries HOT SLOT ids (hot_slot) in
                # place of logical ids — the program itself is the
                # untouched round-17 one, which is the exactness argument.
                # The cost: a sequence's whole table must fit hot during
                # its spec step (ensure_hot raises otherwise) — spec decode
                # does not stream cold pages.
                allb = []
                for i in dslots:
                    allb.extend(self.sched.slots[i].blocks)
                self.pool.ensure_hot(allb)
            for i in dslots:
                s = self.sched.slots[i]
                if tiered:
                    row = [self.pool.hot_slot(b) for b in s.blocks]
                    tables[i, :len(row)] = row
                else:
                    tables[i] = self._table_for(s)
                lengths[i] = s.length
                last[i] = s.pending_token
                temps[i] = s.request.temperature
                seeds[i] = s.request.seed
                limits[i] = len(s.request.prompt) + s.request.max_new_tokens
            if not tiered:
                self._maybe_poison(dslots)
        with span("tpu_ddp.serve.decode.dispatch"):
            k, v, drafted, toks, lps, bad = self._spec(
                self._decode_params, self._draft_params,
                self.pool.k, self.pool.v,
                jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(last), jnp.asarray(temps),
                jnp.asarray(seeds), jnp.asarray(limits))
            self.pool.commit(k, v)
        with self._fetching():
            drafted, toks = np.asarray(drafted), np.asarray(toks)
            lps, bad = np.asarray(lps), np.asarray(bad)
        with span("tpu_ddp.serve.decode.emit"):
            for i in dslots:
                s = self.sched.slots[i]
                req = s.request
                g = accept_length(drafted[i], toks[i], self.spec_k)
                req.spec_proposed += self.spec_k
                self.spec_proposed += self.spec_k
                emitted = 0
                quarantined = False
                for c in range(g + 1):
                    if bad[i, c]:
                        quarantined = True
                        break
                    s.length += 1
                    self._emit(i, int(toks[i, c]), float(lps[i, c]))
                    emitted += 1
                    if req.done:
                        break
                acc = max(emitted - 1, 0)
                req.spec_accepted += acc
                self.spec_accepted += acc
                req.spec_rejected += self.spec_k - acc
                self.spec_rejected += self.spec_k - acc
                if quarantined:
                    self._quarantine(i)
                elif not req.done:
                    # KV rollback: free the tail blocks the rejected
                    # columns over-allocated; garbage beyond ``length``
                    # inside kept blocks is causally masked and the next
                    # step's write at ``length`` overwrites the frontier.
                    self.sched.trim_blocks(i)

    def spec_stats(self) -> dict:
        """The engine's speculation ledger (router stats roll this
        up per replica): knob settings, proposal totals with the
        ``proposed == accepted + rejected`` identity, and the
        acceptance rate (None before any proposal)."""
        p = self.spec_proposed
        return {"spec_k": self.spec_k, "spec_draft": self.spec_draft,
                "decode_quant": self.decode_quant,
                "proposed": p, "accepted": self.spec_accepted,
                "rejected": self.spec_rejected,
                "acceptance": (self.spec_accepted / p) if p else None}

    def _quarantine(self, idx: int) -> None:
        """Non-finite logits on slot ``idx``: isolate the request, not
        the bank. Its private pages are scrubbed before they return to
        the free list — a NaN'd V page re-issued to another request
        would leak through zero-weight attention (0 * NaN = NaN) —
        then the slot retires and the request finishes quarantined."""
        s = self.sched.slots[idx]
        req = s.request
        self.pool.scrub([b for b in s.blocks
                         if self.pool.refcount(b) == 1])
        if self.state.arrays:
            self.state.scrub(idx)
        self.sched.retire(idx)
        req.quarantined = True
        req.done = True
        req.finished_at = time.perf_counter()
        self.metrics.inc("serve_quarantined")
        self._tc(tenant_of(req))["quarantined"] += 1
        self._tc(tenant_of(req))["completed"] += 1
        warnings.warn(
            f"request {req.rid}: non-finite logits at engine step "
            f"{self._step_n}; request quarantined, pages scrubbed",
            stacklevel=3)

    def drain(self) -> list[Request]:
        """Harvest every unfinished request and release all engine
        state (slots retire, pages free back to THIS pool, queue
        clears) — the router's failure-migration hook. Returns the
        harvested requests in submit order so replay elsewhere
        preserves FIFO fairness. The step in flight is handed out
        first, so no sampled token is lost or replayed."""
        self._rest()
        reqs = []
        for i, s in enumerate(self.sched.slots):
            if s is not None:
                reqs.append(s.request)
                self.sched.retire(i)
        reqs.extend(self.sched.queue)
        self.sched.queue.clear()
        harvested = sorted((r for r in reqs if not r.done),
                           key=lambda r: r.rid)
        for r in harvested:
            # The handle migrates to another replica: debit this
            # engine's per-tenant ledger so its local identity holds
            # (the router-level identity follows the handle).
            self._tc(tenant_of(r))["submitted"] -= 1
        return harvested

    def _emit(self, idx: int, tok: int, logprob: float,
              version: int | None = None) -> None:
        """Record one sampled token for slot ``idx``'s request: stream
        it, stamp TTFT on the first, retire on max_new_tokens/EOS.
        ``version`` is the parameter version its step was dispatched
        on (the current one where the step was read back at once)."""
        s = self.sched.slots[idx]
        req = s.request
        s.generated += 1
        s.pending_token = tok
        req.tokens.append(tok)
        req.logprobs.append(logprob)
        req.token_versions.append(
            self.param_version if version is None else version)
        now = time.perf_counter()
        req.token_times.append(now)
        if req.first_token_at is None:
            req.first_token_at = now
            self.metrics.observe("serve_ttft_ms",
                                 (now - req.submitted_at) * 1e3)
        if req.on_token is not None:
            req.on_token(tok)
        if s.generated >= req.max_new_tokens \
                or (req.eos_id is not None and tok == req.eos_id):
            req.done = True
            req.finished_at = now
            self.sched.retire(idx)
            self.metrics.inc("serve_retired")
            self._tc(tenant_of(req))["completed"] += 1


__all__ = ["Request", "ServeEngine"]
