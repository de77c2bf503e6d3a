"""Block-paged KV-cache pool — the serving-side replacement for
``generate.py``'s per-request contiguous ``(B, max_len, KV, hd)``
buffers.

Why paging: a contiguous per-request cache must be sized for the WORST
case (prompt + max_new_tokens), so a fleet of short requests strands
almost all of it. The pool instead holds one device buffer of
fixed-size blocks per layer — ``(num_layers, num_blocks, block_size,
KV*hd)`` — and each live request owns a list of block ids (its "block
table"). A position's row holds its K/V heads side by side (head ``h``
is columns ``[h*hd, (h+1)*hd)``), so a page is one contiguous
``(block_size, KV*hd)`` tile run that a single DMA moves and the
paged decode kernel (ops/pallas/paged_attention.py) reads in place;
:func:`rows` / :func:`gather_view` convert at the write and gather
sites. The programs that gather take a layer's pages from the WHOLE
pool in one gather (``pool[li, tables]``, :func:`gather_view`) and
never slice the layer out first: a static slice of a program's
argument is a copy on XLA:TPU, one whole layer of pages each time
(DESIGN.md §19). Blocks are allocated lazily as a sequence grows and
returned on retirement, so cache memory tracks the LIVE token count,
not the worst case, and the same HBM serves many more concurrent
sequences (the vLLM PagedAttention argument).

Accounting is host-side and exact, and deliberately simple: a free
list of block ids plus a per-block REFCOUNT. Block 0 is the NULL block
— never allocated, never freed. It is where the jitted steps redirect
every masked write (idle decode slots, prefill padding), so
out-of-range scatters land in a sacrificial page instead of a page
owned by another request; its contents are garbage by design and are
never attended (the causal position mask in ``decode.attend_cached``
zeroes any read beyond a query's own length).

Refcounts are what makes prefix caching (tpu_ddp/fleet/prefix.py)
safe: a block holding a shared system prompt's KV appears in MANY
block tables at once (plus the prefix index itself), and is returned
to the free list only when the LAST holder drops it. ``free`` is
therefore a decref; ``incref`` adds a holder; ``cow`` gives a writer
its own copy of a shared block before it diverges. The accounting
identity generalizes from round 12's
``free + Σ live block-table lengths == total usable`` to
``free + Σ unique-allocated == total usable`` with per-block
refcounts equal to the number of holders — :meth:`refcount_ok` checks
exactly that, and double-free / null-free / negative-refcount all
still raise rather than corrupt.

Cache dtype rides the SAME policy vocabulary as training's saved
activations (tpu_ddp/memory/policy.py): "compute" stores what the
model computes in (exactness-preserving, the default), "bf16" halves
cache bytes under an f32 compute model (decode is KV-read-bound, so
this is a real knob), "f32" forces full precision.

Tiers (round 18, DESIGN.md §27): ``tiers > 1`` splits RESIDENCY from
ALLOCATION. Block ids stay logical — the scheduler, prefix index,
refcounts and every block table are unchanged — but a logical block's
PAGES live in one of three places:

- **hot** (tier 1): an HBM slot in the exact cache dtype, the only
  tier the jitted steps read directly or write at all. Capacity
  ``hbm_blocks - 1`` (slot 0 is the hot null page).
- **cold** (tier 2, ``tiers >= 2``): an HBM slot quantized by the
  cold-page codec (parallel/compress.py page_quantize — per-token-row
  int8 + f32 scale, or a bf16 downcast). The tiered step programs
  (serve/long_context.py) read cold pages THROUGH the dequant, so a
  long context decodes without ever being fully hot.
- **spill** (tier 3, ``tiers == 3``): host memory, holding the
  already-quantized page. Spilled pages are invisible to the device;
  ``ensure_device`` promotes them back to cold on demand.

Movement is demand-driven and batched: ``ensure_hot`` promotes
(dequant program, hot buffers donated), demotes LRU victims (quantize
program, cold buffers donated) and spills LRU cold pages to host when
the cold tier is also full. A FRESH block (allocated, never written)
has no residency until its first ``ensure_hot`` — reusing a hot slot's
stale finite garbage is safe by the same causal-mask doctrine as the
null block. The per-tier accounting identity extends the round-12
one: ``hot_free + hot_resident == hbm usable`` and likewise for cold,
with hot + cold + spill + fresh partitioning exactly the allocated
ids (:meth:`tier_accounting_ok`, folded into :meth:`refcount_ok`).

At ``tiers == 1`` every code path below is the round-12 pool
unchanged — same buffers, same ops, same device programs — which is
what keeps every existing pool consumer's bitwise-parity suite
meaningful against this refactor.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from tpu_ddp.memory.policy import resolve_act_dtype
from tpu_ddp.parallel.compress import page_dequantize, page_quantize

COLD_DTYPES = {"int8": jnp.int8, "bf16": jnp.bfloat16}


def pin_committed(tree):
    """``device_put`` every leaf onto its own sharding — a no-move
    commit. jit cache keys distinguish committed from uncommitted
    arguments, and a weight-streaming flip (publish/subscriber.py)
    always yields committed params; engine state that starts
    uncommitted would therefore force a one-time recompile of the step
    programs on the first request after a flip. Pinning at
    construction keeps one cache key for the engine's whole life."""
    return jax.tree.map(lambda x: jax.device_put(x, x.sharding), tree)


def rows(x):
    """(..., KV, hd) K or V -> the pool's (..., KV*hd) row form."""
    return x.reshape(x.shape[:-2] + (-1,))


def gather_view(pool, li: int, tables, model):
    """Layer ``li``'s pages gathered through ``tables`` (S, BPS) into
    the contiguous (S, BPS*block_size, KV, hd) view
    ``decode.attend_cached`` reads.

    ``pool[li, tables]`` is ONE gather on the whole pool. Slicing the
    layer out first (``pool[li]``, then ``[tables]``) gives the same
    values, but a static slice of a program's argument is a copy on
    XLA:TPU: it materialised all N pages of the layer (67 MB of the
    benchmark's pool, once for K and once for V in each of 30 layers)
    to read the BPS that ``tables`` names. No step program slices a
    layer out of a pool (tests/test_serve.py holds them to that)."""
    pages = pool[li, tables]
    return pages.reshape(pages.shape[0], -1, model.kv_heads,
                         model.head_dim)


def _pad_width(n: int) -> int:
    """Round a movement batch up to a power of two: slot vectors pad
    with slot 0 (the null page is sacrificial on BOTH tiers), so the
    jit cache holds O(log) demote/promote programs, not one per batch
    size the allocator happens to produce."""
    w = 1
    while w < n:
        w *= 2
    return w


@functools.partial(jax.jit, donate_argnums=(2, 3, 4, 5))
def _demote_prog(hot_k, hot_v, cold_k, cold_v, cold_sk, cold_sv,
                 hot_slots, cold_slots):
    """HOT -> COLD: gather hot pages, quantize (page_quantize), scatter
    into cold slots. Hot buffers are read-only (the host just frees
    the slots); cold buffers are donated — demotion is in-place on the
    cold tier."""
    qk, sk = page_quantize(hot_k[:, hot_slots], cold_k.dtype)
    qv, sv = page_quantize(hot_v[:, hot_slots], cold_v.dtype)
    cold_k = cold_k.at[:, cold_slots].set(qk)
    cold_v = cold_v.at[:, cold_slots].set(qv)
    cold_sk = cold_sk.at[:, cold_slots].set(sk)
    cold_sv = cold_sv.at[:, cold_slots].set(sv)
    return cold_k, cold_v, cold_sk, cold_sv


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _promote_prog(hot_k, hot_v, cold_k, cold_v, cold_sk, cold_sv,
                  hot_slots, cold_slots):
    """COLD -> HOT: gather cold pages + scales, dequantize into the
    hot dtype, scatter into hot slots (hot buffers donated)."""
    hot_k = hot_k.at[:, hot_slots].set(page_dequantize(
        cold_k[:, cold_slots], cold_sk[:, cold_slots], hot_k.dtype))
    hot_v = hot_v.at[:, hot_slots].set(page_dequantize(
        cold_v[:, cold_slots], cold_sv[:, cold_slots], hot_v.dtype))
    return hot_k, hot_v


class PagedKVPool:
    """One paged K and V buffer covering every layer of one model.

    The device arrays are FUNCTIONAL state: the engine passes
    ``pool.k`` / ``pool.v`` into its jitted steps (donated) and stores
    the returned buffers back via :meth:`commit`. The pool object owns
    only the allocator — which block ids are free, and (``tiers > 1``)
    which tier each allocated id is resident in — so allocator bugs
    are ordinary host Python, debuggable without a device.
    """

    NULL_BLOCK = 0

    def __init__(self, model, num_blocks: int, block_size: int,
                 cache_dtype: str = "compute", *, tiers: int = 1,
                 cold_dtype: str = "int8",
                 hbm_blocks: int | None = None,
                 cold_blocks: int | None = None):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {num_blocks}")
        if tiers not in (1, 2, 3):
            raise ValueError(f"tiers must be 1, 2 or 3, got {tiers!r} "
                             "(TPU_DDP_KV_TIERS)")
        if cold_dtype not in COLD_DTYPES:
            raise ValueError(
                f"cold_dtype={cold_dtype!r}: expected one of "
                f"{sorted(COLD_DTYPES)} (TPU_DDP_KV_COLD_DTYPE)")
        self.model = model
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.tiers = tiers
        self.cold_dtype_name = cold_dtype
        self.dtype = resolve_act_dtype(cache_dtype, model.compute_dtype)
        # Pages for the layers that keep K/V only: a layer whose mixer
        # keeps recurrent state has none (its state is in the StatePool).
        self.layers = sum(m == "attention" for m in model.mixers)
        page = (block_size, model.kv_heads * model.head_dim)
        # Hot buffers: at tiers == 1 the logical id IS the hot slot
        # (identity map, num_blocks slots) — the round-12 layout,
        # bitwise. At tiers > 1 hot capacity shrinks to hbm_blocks and
        # block tables translate through _hot_slot.
        self.hbm_blocks = (num_blocks if tiers == 1
                           else int(hbm_blocks if hbm_blocks is not None
                                    else num_blocks))
        self.cold_blocks = int(cold_blocks if cold_blocks is not None
                               else num_blocks) if tiers > 1 else 0
        if tiers > 1 and self.hbm_blocks < 2:
            raise ValueError("hbm_blocks must be >= 2 (slot 0 is the "
                             f"hot null page), got {self.hbm_blocks}")
        if tiers > 1 and self.cold_blocks < 2:
            raise ValueError("cold_blocks must be >= 2 (slot 0 is the "
                             f"cold null page), got {self.cold_blocks}")
        shape = (self.layers, self.hbm_blocks) + page
        self.k = pin_committed(jnp.zeros(shape, self.dtype))
        self.v = pin_committed(jnp.zeros(shape, self.dtype))
        self.cold_k = self.cold_v = None
        self.cold_sk = self.cold_sv = None
        if tiers > 1:
            cshape = (self.layers, self.cold_blocks) + page
            cdt = COLD_DTYPES[cold_dtype]
            self.cold_k = pin_committed(jnp.zeros(cshape, cdt))
            self.cold_v = pin_committed(jnp.zeros(cshape, cdt))
            sshape = (self.layers, self.cold_blocks, block_size)
            self.cold_sk = pin_committed(jnp.zeros(sshape, jnp.float32))
            self.cold_sv = pin_committed(jnp.zeros(sshape, jnp.float32))
        # LIFO free list: recently-freed (still-hot) pages are reused
        # first. Block 0 is never a member.
        self._free = list(range(num_blocks - 1, 0, -1))
        # refs[b] == number of holders (block tables + prefix-index
        # entries) for an allocated block; 0 for free blocks and the
        # null block.
        self._refs = [0] * num_blocks
        # Residency maps (tiers > 1): tier name per logical id, the
        # hot/cold slot it occupies (0 = none), per-tier slot free
        # lists, LRU orderings (index 0 = coldest candidate) and the
        # host spill store of already-quantized pages.
        self._tier = ["free"] * num_blocks
        self._hot_slot = [0] * num_blocks
        self._cold_slot = [0] * num_blocks
        self._hot_free = (list(range(self.hbm_blocks - 1, 0, -1))
                          if tiers > 1 else [])
        self._cold_free = (list(range(self.cold_blocks - 1, 0, -1))
                           if tiers > 1 else [])
        self._hot_lru: list[int] = []
        self._cold_lru: list[int] = []
        self._spill: dict[int, tuple] = {}
        # Optional last-resort reclaimer (the prefix index registers
        # itself here): consulted when the free list runs dry, it may
        # drop index-only holders to turn evictable blocks into free
        # ones. Interface: ``.evictable_count`` (int property) and
        # ``.reclaim(n) -> int`` (blocks actually freed).
        self.reclaimer = None

    # ---- allocator -----------------------------------------------------

    @property
    def total_usable(self) -> int:
        """Allocatable blocks (the null block is not one)."""
        return self.num_blocks - 1

    @property
    def hot_usable(self) -> int:
        """Hot (HBM, exact-dtype) pages available to residency — what
        bounds the SIMULTANEOUSLY-hot context, not total context."""
        return self.hbm_blocks - 1

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def allocatable(self) -> int:
        """Blocks an admission may count on: free now, plus what the
        reclaimer could evict on demand (prefix-index entries nobody
        else holds). This — not ``free_count`` — is what the
        scheduler's reservation rule budgets against once a prefix
        index is attached, otherwise cold cache entries would block
        admission forever."""
        extra = self.reclaimer.evictable_count if self.reclaimer else 0
        return len(self._free) + extra

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache slots."""
        return math.ceil(n_tokens / self.block_size)

    def alloc(self) -> int:
        """Claim one free block id (refcount 1). The scheduler's
        reservation rule (tpu_ddp/serve/scheduler.py) guarantees this
        never raises for an admitted request; raising (not waiting)
        keeps the bug loud if that invariant is ever broken."""
        if not self._free and self.reclaimer is not None:
            self.reclaimer.reclaim(1)
        if not self._free:
            raise RuntimeError(
                "KV pool exhausted — the scheduler admitted more "
                "worst-case tokens than the pool holds (reservation "
                "accounting bug)")
        b = self._free.pop()
        self._refs[b] = 1
        if self.tiers > 1:
            # FRESH: allocated, no residency, no content. The first
            # ensure_hot gives it a hot slot (stale finite garbage in
            # a reused slot is causally masked, like the null page).
            self._tier[b] = "fresh"
        return b

    def refcount(self, b: int) -> int:
        return self._refs[b]

    def incref(self, blocks) -> None:
        """Add one holder to each block (prefix-index registration, or
        a new request sharing cached prompt blocks)."""
        for b in blocks:
            self._check_id(b)
            if self._refs[b] == 0:
                raise ValueError(
                    f"incref of unallocated block {b} — a holder can "
                    "only be added to a live block")
            self._refs[b] += 1

    def free(self, blocks) -> None:
        """Drop one holder per block; a block returns to the free list
        when its LAST holder lets go — releasing whatever tier slot
        (or host spill entry) its pages occupied. Double-free (decref
        below zero) and null-free are accounting corruption, not
        recoverable states — raise."""
        for b in blocks:
            self._check_id(b)
            if self._refs[b] == 0:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                if self.tiers > 1:
                    self._release_residency(b)
                self._free.append(b)

    def _release_residency(self, b: int) -> None:
        t = self._tier[b]
        if t == "hot":
            self._hot_free.append(self._hot_slot[b])
            self._hot_slot[b] = 0
            self._hot_lru.remove(b)
        elif t == "cold":
            self._cold_free.append(self._cold_slot[b])
            self._cold_slot[b] = 0
            self._cold_lru.remove(b)
        elif t == "spill":
            del self._spill[b]
        self._tier[b] = "free"

    def cow(self, b: int):
        """Copy-on-write: give the caller a PRIVATE copy of shared
        block ``b`` (refcount 1 on the copy; ``b``'s refcount is
        untouched — the caller still drops its own share). The device
        copy happens once, at admission, off the decode hot path.
        Tiered: the source promotes to hot first (the copy must be
        exact-dtype — quantizing a shared prompt on copy would fork
        its numerics), and the copy is born hot."""
        self._check_id(b)
        if self._refs[b] == 0:
            raise ValueError(f"copy-on-write of unallocated block {b}")
        new = self.alloc()
        if self.tiers == 1:
            self.k = self.k.at[:, new].set(self.k[:, b])
            self.v = self.v.at[:, new].set(self.v[:, b])
            return new
        self.ensure_hot([b, new])
        sb, sn = self._hot_slot[b], self._hot_slot[new]
        self.k = self.k.at[:, sn].set(self.k[:, sb])
        self.v = self.v.at[:, sn].set(self.v[:, sb])
        return new

    def _check_id(self, b: int) -> None:
        if b == self.NULL_BLOCK:
            raise ValueError("the null block is never allocated, "
                             "freed, or shared")
        if not 0 < b < self.num_blocks:
            raise ValueError(f"block id {b} out of range")

    def refcount_ok(self, holders) -> bool:
        """The extended accounting identity. ``holders`` is an
        iterable of block-id lists — every live block table plus the
        prefix index's held set. Checks (a) each block's refcount
        equals its number of appearances, (b) free blocks have no
        holders, (c) ``free + Σ unique-allocated == total``, and
        (d) the per-tier residency identity (trivially true at
        ``tiers == 1``)."""
        counts = [0] * self.num_blocks
        for hold in holders:
            for b in hold:
                counts[b] += 1
        if counts[self.NULL_BLOCK]:
            return False
        for b in range(1, self.num_blocks):
            if counts[b] != self._refs[b]:
                return False
            if counts[b] and b in self._free:
                return False
        unique = sum(1 for b in range(1, self.num_blocks) if counts[b])
        if self.free_count + unique != self.total_usable:
            return False
        return self.tier_accounting_ok()

    # ---- tiers ---------------------------------------------------------

    def tier_of(self, b: int) -> str:
        """"hot" | "cold" | "spill" | "fresh" for an allocated block,
        "free" otherwise. At ``tiers == 1`` every allocated block is
        hot by construction (the buffers ARE the hot tier)."""
        self._check_id(b)
        if self.tiers == 1:
            return "hot" if self._refs[b] else "free"
        return self._tier[b]

    def tier_counts(self) -> dict:
        """Per-tier census (tests, bench, sweep telemetry)."""
        if self.tiers == 1:
            hot = sum(1 for r in self._refs[1:] if r)
            return {"hot": hot, "cold": 0, "spill": 0, "fresh": 0,
                    "hot_free": self.free_count, "cold_free": 0}
        c = {"hot": 0, "cold": 0, "spill": 0, "fresh": 0}
        for b in range(1, self.num_blocks):
            if self._tier[b] in c:
                c[self._tier[b]] += 1
        c["hot_free"] = len(self._hot_free)
        c["cold_free"] = len(self._cold_free)
        return c

    def tier_accounting_ok(self) -> bool:
        """The per-tier residency identity (satellite of §27):
        ``hot_free + hot_resident == hot usable`` and the cold-tier
        analog; hot/cold/spill/fresh partition exactly the allocated
        ids; slot maps are injective and consistent with the LRU
        orderings and the host spill store."""
        if self.tiers == 1:
            return True
        tiers: dict[str, list[int]] = {
            "hot": [], "cold": [], "spill": [], "fresh": [], "free": []}
        for b in range(1, self.num_blocks):
            if self._tier[b] not in tiers:
                return False
            tiers[self._tier[b]].append(b)
            if (self._refs[b] == 0) != (self._tier[b] == "free"):
                return False
        if len(self._hot_free) + len(tiers["hot"]) != self.hot_usable:
            return False
        if len(self._cold_free) + len(tiers["cold"]) \
                != self.cold_blocks - 1:
            return False
        hot_slots = [self._hot_slot[b] for b in tiers["hot"]]
        cold_slots = [self._cold_slot[b] for b in tiers["cold"]]
        if 0 in hot_slots or len(set(hot_slots)) != len(hot_slots):
            return False
        if 0 in cold_slots or len(set(cold_slots)) != len(cold_slots):
            return False
        if set(hot_slots) & set(self._hot_free):
            return False
        if set(cold_slots) & set(self._cold_free):
            return False
        if sorted(self._hot_lru) != sorted(tiers["hot"]):
            return False
        if sorted(self._cold_lru) != sorted(tiers["cold"]):
            return False
        if sorted(self._spill) != sorted(tiers["spill"]):
            return False
        for name in ("hot", "cold", "spill", "fresh"):
            for b in tiers[name]:
                if self._hot_slot[b] and name != "hot":
                    return False
                if self._cold_slot[b] and name != "cold":
                    return False
        return True

    def ensure_device(self, blocks) -> None:
        """Bring spilled blocks back to the device (SPILL -> COLD) —
        the precondition for appearing in a step program's cold table.
        Hot/cold/fresh blocks are untouched (reads of a fresh block's
        null slots are causally masked, so fresh needs no residency
        until its first write)."""
        if self.tiers < 3:
            return
        ids = [b for b in dict.fromkeys(blocks)
               if self._tier[b] == "spill"]
        if ids:
            self._unspill(ids, protect=set(blocks))

    def hot_slot(self, b: int) -> int:
        """The hot-tier slot of a HOT block — what a compiled step
        that addresses the hot buffer directly (the fused speculative
        program's all-hot translation, chaos poison) writes into its
        table. At ``tiers == 1`` the logical id IS the slot."""
        if self.tiers == 1:
            return b
        if self._tier[b] != "hot":
            raise RuntimeError(f"block {b} is {self._tier[b]}, not hot "
                               "— ensure_hot first")
        return self._hot_slot[b]

    def ensure_hot(self, blocks, keep=()) -> None:
        """Demand promotion: after this call every block in ``blocks``
        is HOT (exact dtype, scatter-writable). Promotes cold pages
        through the dequant program, pulls spilled pages to cold
        first, gives fresh blocks a slot with no data movement, and
        demotes LRU hot victims (never one of ``blocks``) to make
        room. ``keep`` names blocks that must stay DEVICE-resident
        (demoting them to cold is fine, spilling them to host is not)
        — the rest of the step's read set. Raises loudly when
        ``blocks`` alone exceeds hot capacity — the caller asked for a
        simultaneous working set the HBM budget cannot hold, a sizing
        bug, not a pressure state."""
        if self.tiers == 1:
            return
        ids = list(dict.fromkeys(blocks))
        for b in ids:
            self._check_id(b)
            if self._refs[b] == 0:
                raise ValueError(f"ensure_hot of unallocated block {b}")
        if len(ids) > self.hot_usable:
            raise RuntimeError(
                f"ensure_hot of {len(ids)} blocks exceeds the hot "
                f"tier's {self.hot_usable} usable pages (hbm_blocks="
                f"{self.hbm_blocks}) — shrink the simultaneous "
                "working set or raise the HBM budget")
        protect = set(ids)
        on_device = protect | set(keep)
        need = [b for b in ids if self._tier[b] != "hot"]
        spilled = [b for b in need if self._tier[b] == "spill"]
        if spilled:
            self._unspill(spilled, on_device)
        deficit = len(need) - len(self._hot_free)
        if deficit > 0:
            victims = [b for b in self._hot_lru if b not in protect]
            if len(victims) < deficit:
                raise RuntimeError(
                    "hot tier wedged: not enough evictable pages to "
                    f"promote {len(need)} blocks (hbm_blocks="
                    f"{self.hbm_blocks})")
            self._demote(victims[:deficit], on_device)
        promote = [b for b in need if self._tier[b] == "cold"]
        fresh = [b for b in need if self._tier[b] == "fresh"]
        for b in need:
            slot = self._hot_free.pop()
            self._hot_slot[b] = slot
            self._hot_lru.append(b)
        if promote:
            n = len(promote)
            w = _pad_width(n)
            hs = np.zeros(w, np.int32)
            cs = np.zeros(w, np.int32)
            hs[:n] = [self._hot_slot[b] for b in promote]
            cs[:n] = [self._cold_slot[b] for b in promote]
            self.k, self.v = _promote_prog(
                self.k, self.v, self.cold_k, self.cold_v,
                self.cold_sk, self.cold_sv,
                jnp.asarray(hs), jnp.asarray(cs))
            for b in promote:
                self._cold_free.append(self._cold_slot[b])
                self._cold_slot[b] = 0
                self._cold_lru.remove(b)
        for b in promote + fresh:
            self._tier[b] = "hot"
        self._touch(ids)

    def _touch(self, blocks) -> None:
        """LRU bump: mark hot blocks as most-recently used."""
        for b in blocks:
            if self._tier[b] == "hot":
                self._hot_lru.remove(b)
                self._hot_lru.append(b)

    def _demote(self, blocks, protect) -> None:
        """HOT -> COLD for ``blocks`` (one quantize program), spilling
        LRU cold pages to host first if the cold tier is full."""
        self._grab_cold(len(blocks), protect)
        n = len(blocks)
        w = _pad_width(n)
        hs = np.zeros(w, np.int32)
        cs = np.zeros(w, np.int32)
        hs[:n] = [self._hot_slot[b] for b in blocks]
        new_cold = [self._cold_free.pop() for _ in blocks]
        cs[:n] = new_cold
        self.cold_k, self.cold_v, self.cold_sk, self.cold_sv = \
            _demote_prog(self.k, self.v, self.cold_k, self.cold_v,
                         self.cold_sk, self.cold_sv,
                         jnp.asarray(hs), jnp.asarray(cs))
        for b, slot in zip(blocks, new_cold):
            self._hot_free.append(self._hot_slot[b])
            self._hot_slot[b] = 0
            self._hot_lru.remove(b)
            self._tier[b] = "cold"
            self._cold_slot[b] = slot
            self._cold_lru.append(b)

    def _grab_cold(self, n: int, protect) -> None:
        """Guarantee >= n free cold slots, spilling LRU cold pages to
        host (tiers == 3) — at tiers == 2 running out is terminal."""
        deficit = n - len(self._cold_free)
        if deficit <= 0:
            return
        victims = [b for b in self._cold_lru if b not in protect]
        if self.tiers < 3 or len(victims) < deficit:
            raise RuntimeError(
                "cold tier exhausted: no host spill tier to evict "
                "into (tiers=2) or nothing evictable — raise "
                "cold_blocks or use tiers=3" if self.tiers < 3 else
                "cold tier wedged: every cold page is protected")
        self._spill_out(victims[:deficit])

    def _spill_out(self, blocks) -> None:
        """COLD -> SPILL: fetch the already-quantized pages to host in
        one device round trip and free the cold slots. No device
        program runs — the quantization happened at demote time."""
        idx = np.asarray([self._cold_slot[b] for b in blocks], np.int32)
        kq = np.asarray(self.cold_k[:, idx])
        vq = np.asarray(self.cold_v[:, idx])
        sk = np.asarray(self.cold_sk[:, idx])
        sv = np.asarray(self.cold_sv[:, idx])
        for i, b in enumerate(blocks):
            self._spill[b] = (kq[:, i], vq[:, i], sk[:, i], sv[:, i])
            self._cold_free.append(self._cold_slot[b])
            self._cold_slot[b] = 0
            self._cold_lru.remove(b)
            self._tier[b] = "spill"

    def _unspill(self, blocks, protect) -> None:
        """SPILL -> COLD: scatter the host copies back into cold
        slots (one device round trip for the batch)."""
        self._grab_cold(len(blocks), set(protect) | set(blocks))
        slots = [self._cold_free.pop() for _ in blocks]
        idx = jnp.asarray(np.asarray(slots, np.int32))
        kq = np.stack([self._spill[b][0] for b in blocks], axis=1)
        vq = np.stack([self._spill[b][1] for b in blocks], axis=1)
        sk = np.stack([self._spill[b][2] for b in blocks], axis=1)
        sv = np.stack([self._spill[b][3] for b in blocks], axis=1)
        self.cold_k = self.cold_k.at[:, idx].set(jnp.asarray(kq))
        self.cold_v = self.cold_v.at[:, idx].set(jnp.asarray(vq))
        self.cold_sk = self.cold_sk.at[:, idx].set(jnp.asarray(sk))
        self.cold_sv = self.cold_sv.at[:, idx].set(jnp.asarray(sv))
        for b, slot in zip(blocks, slots):
            del self._spill[b]
            self._tier[b] = "cold"
            self._cold_slot[b] = slot
            self._cold_lru.append(b)

    def slot_tables(self, blocks, width: int):
        """Translate a logical block table into the tiered step
        programs' two physical tables: (hot_slots, cold_slots), each
        ``(width,)`` int32, zero where the block is not in that tier
        (slot 0 reads the sacrificial null page). Spilled blocks are
        a caller bug — ``ensure_device`` first."""
        hot = np.zeros(width, np.int32)
        cold = np.zeros(width, np.int32)
        if self.tiers == 1:
            # Flat pool: logical id IS the hot slot.
            hot[:len(list(blocks))] = np.asarray(list(blocks), np.int32)
            return hot, cold
        for i, b in enumerate(blocks):
            t = self._tier[b]
            if t == "hot":
                hot[i] = self._hot_slot[b]
            elif t == "cold":
                cold[i] = self._cold_slot[b]
            elif t == "spill":
                raise RuntimeError(
                    f"block {b} is spilled to host — ensure_device "
                    "before building step tables")
        return hot, cold

    def page_arrays(self, blocks):
        """Device views of ``blocks``' pages in the EXACT cache dtype,
        shaped (L, n, bs, KV*hd) — the disagg ship path and any other
        consumer that reads whole pages. Tiered pools promote to hot
        first: page readers get exact bytes, never a dequantized
        approximation the hot tier itself wouldn't serve."""
        ids = list(blocks)
        if self.tiers > 1:
            self.ensure_hot(ids)
            ids = [self._hot_slot[b] for b in ids]
        idx = jnp.asarray(np.asarray(ids, np.int32))
        return self.k[:, idx], self.v[:, idx]

    def scrub(self, blocks) -> None:
        """Zero the device pages of ``blocks`` WHEREVER they are
        resident. Ordinary stale garbage in a reused page is harmless
        (finite values beyond a query's length get exactly-zero
        attention weight), but NON-FINITE garbage is not: the V-side
        product ``0 * NaN = NaN`` leaks through the causal mask into
        every query that merely shares the page. Quarantine
        (serve/engine.py) therefore scrubs a poisoned request's
        private pages before freeing them — and a poisoned page that
        was demoted or spilled carries its NaNs through the quantizer,
        so every tier scrubs."""
        blocks = list(blocks)
        if not blocks:
            return
        if self.tiers == 1:
            ids = jnp.asarray(np.asarray(blocks, np.int32))
            self.k = self.k.at[:, ids].set(0)
            self.v = self.v.at[:, ids].set(0)
            return
        hot = [self._hot_slot[b] for b in blocks
               if self._tier[b] == "hot"]
        cold = [self._cold_slot[b] for b in blocks
                if self._tier[b] == "cold"]
        if hot:
            ids = jnp.asarray(np.asarray(hot, np.int32))
            self.k = self.k.at[:, ids].set(0)
            self.v = self.v.at[:, ids].set(0)
        if cold:
            ids = jnp.asarray(np.asarray(cold, np.int32))
            self.cold_k = self.cold_k.at[:, ids].set(0)
            self.cold_v = self.cold_v.at[:, ids].set(0)
            self.cold_sk = self.cold_sk.at[:, ids].set(0)
            self.cold_sv = self.cold_sv.at[:, ids].set(0)
        for b in blocks:
            if self._tier[b] == "spill":
                self._spill[b] = tuple(np.zeros_like(a)
                                       for a in self._spill[b])

    # ---- device state --------------------------------------------------

    def commit(self, k, v) -> None:
        """Store the jitted step's updated (hot) buffers (the old ones
        were donated into the step)."""
        self.k, self.v = k, v


class StatePool:
    """The recurrent state of every slot, beside the paged K/V: for a
    model whose layers keep a fixed-size state per sequence and not a
    list of positions (``model.state_shapes``), one array per kind of
    state, ``(state layers, num_slots, ...)``, indexed by SLOT. There is
    no allocator: a slot's state exists for as long as the slot does,
    and there is no null slot to send a masked write to, so the step
    programs keep three rules themselves (serve/engine.py): a decode step
    leaves untouched the state of every slot it has no row for, a
    request's first chunk starts from zero whatever the slot's last
    tenant left, and padding does not advance it.

    Like the K/V pool the arrays are FUNCTIONAL state: ``pool.arrays``
    goes into the jitted steps (donated, updated in place) and comes back
    through :meth:`commit`. For a model without such layers ``arrays`` is
    an empty dict, which adds no argument to a program."""

    def __init__(self, model, num_slots: int):
        self.arrays = pin_committed({
            name: jnp.zeros(s.shape, s.dtype)
            for name, s in model.state_shapes(num_slots).items()})

    @property
    def nbytes(self) -> int:
        return sum(a.nbytes for a in self.arrays.values())

    def commit(self, arrays=None) -> None:
        """Store what a jitted step returned for the donated arrays
        (nothing, from a program of a model without such state)."""
        if arrays is not None:
            self.arrays = arrays

    def scrub(self, slot: int) -> None:
        """Zero slot ``slot``'s state in every layer (quarantine: a
        non-finite state is not left where a reader could find it, though
        the next tenant's first chunk would not read it)."""
        self.arrays = {name: a.at[:, slot].set(0)
                       for name, a in self.arrays.items()}
