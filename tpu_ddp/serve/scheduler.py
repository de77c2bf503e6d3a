"""Iteration-level (continuous-batching) scheduler.

The Orca/vLLM scheduling idea: batch membership is re-decided EVERY
model step, not per batch-of-requests. A fixed number of decode slots
runs one jitted whole-batch decode step per iteration; finished
sequences retire and their slot + KV blocks are reusable on the very
next step, so a long request never holds short ones hostage and the
batch stays full under load. Prefill is chunked (``prefill_chunk``
tokens per step) and interleaved — at most ONE chunk per engine step —
so a long prompt cannot head-of-line-block the live decode batch for
more than one chunk's latency.

Invariants (docs/DESIGN.md §19, pinned by tests/test_serve.py):

- **FIFO admission / no starvation.** Requests admit strictly in
  submit order; if the queue head does not fit, nothing behind it is
  admitted either. Retirement monotonically frees blocks, so the head
  always eventually fits (its feasibility was checked at submit) —
  no request waits forever behind later arrivals.
- **Admitted requests always finish.** Admission reserves the WORST
  CASE block count ``ceil((prompt + max_new) / block_size)`` against
  ``pool.allocatable`` minus every live request's still-unallocated
  reservation. Blocks are then allocated lazily as the sequence grows,
  but the reservation means mid-flight allocation can never fail —
  no deadlock where live requests starve each other out of pages.
  With a prefix index attached the reservation ledger charges every
  draw an admission can make on ``free + evictable``: fresh blocks
  (``need`` minus cached hits, plus one for a CoW copy) AND each hit
  block whose share converts an evictable cache entry into a pinned
  one. Nothing else ever shrinks ``free + evictable``, so lazy
  mid-flight allocation still cannot fail.
- **Page-pool accounting.** ``free + Σ unique-allocated == total
  usable`` at every step with per-block refcounts equal to holder
  counts (``pool.refcount_ok``); retirement drops exactly one holder
  per allocated block (pool raises on double free / null free).

``mode="static"`` is the experiment baseline, NOT a production path:
admission waits until EVERY slot is idle, fills all slots from the
queue, then admits nothing until the whole batch drains — classic
static batching, with all other machinery identical, so the serve
sweep's continuous-vs-static comparison isolates exactly the
scheduling policy.

Multi-tenancy (docs/DESIGN.md §25, ``TPU_DDP_TENANT_CLASSES``): with
tenant classes configured, admission switches from global FIFO to
WEIGHTED FAIR QUEUEING over per-tenant FIFO heads — stride scheduling:
each tenant carries a virtual ``pass`` that advances by
``work / weight`` per admission, and the tenant with the smallest pass
admits next. A weight-3 tenant therefore gets 3x the admission
bandwidth of a weight-1 tenant under contention, while FIFO order holds
WITHIN each tenant and an idle tenant re-joins at the current virtual
time (it cannot hoard credit and then starve everyone). Classes also
carry an optional per-request TTFT deadline (queued past it = shed, the
engine enforces it) and an optional outstanding-token budget (a tenant
at its budget is passed over for admission until its own work retires —
one tenant cannot monopolize the slot bank no matter its arrival rate).
The accounting identity ``completed + cancelled + shed == submitted``
is tracked and enforced PER TENANT by the engine.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any

DEFAULT_TENANT = "default"


@dataclasses.dataclass(frozen=True)
class TenantClass:
    """One tenant's SLO class: WFQ weight (higher = more admission
    bandwidth, and sheds LAST under pressure), optional queued-TTFT
    deadline, optional outstanding-token budget."""

    name: str
    weight: int = 1
    deadline_ms: float = 0.0   # 0 = no per-class queue deadline
    token_budget: int = 0      # 0 = unbounded outstanding tokens

    def __post_init__(self):
        if not self.name:
            raise ValueError("tenant class needs a non-empty name")
        if self.weight < 1:
            raise ValueError(
                f"tenant {self.name!r}: weight must be >= 1, "
                f"got {self.weight}")
        if self.deadline_ms < 0:
            raise ValueError(
                f"tenant {self.name!r}: deadline_ms must be >= 0")
        if self.token_budget < 0:
            raise ValueError(
                f"tenant {self.name!r}: token_budget must be >= 0")


def parse_tenant_classes(spec: str | None) -> dict[str, TenantClass]:
    """Parse a ``TPU_DDP_TENANT_CLASSES`` value: comma-separated
    ``name=weight[:deadline_ms[:token_budget]]`` entries. Empty/None
    means no classes (single anonymous tenant, plain FIFO). Raises
    ValueError with the offending entry on malformed input — a typo'd
    class silently running as weight-1 would fake SLO coverage."""
    out: dict[str, TenantClass] = {}
    if not spec:
        return out
    for entry in str(spec).split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, eq, rest = entry.partition("=")
        name = name.strip()
        if not eq or not name:
            raise ValueError(
                f"bad tenant class {entry!r}: expected "
                "name=weight[:deadline_ms[:token_budget]]")
        if name in out:
            raise ValueError(f"duplicate tenant class {name!r}")
        parts = rest.split(":")
        if len(parts) > 3:
            raise ValueError(
                f"bad tenant class {entry!r}: at most "
                "weight:deadline_ms:token_budget")
        try:
            weight = int(parts[0])
            deadline = float(parts[1]) if len(parts) > 1 and parts[1] \
                else 0.0
            budget = int(parts[2]) if len(parts) > 2 and parts[2] else 0
        except ValueError:
            raise ValueError(
                f"bad tenant class {entry!r}: expected "
                "name=weight[:deadline_ms[:token_budget]] with "
                "numeric fields") from None
        out[name] = TenantClass(name, weight, deadline, budget)
    return out


def tenant_of(request) -> str:
    """The tenant a request belongs to (engine-independent handles
    from older call sites default to the anonymous tenant)."""
    return getattr(request, "tenant", DEFAULT_TENANT)


@dataclasses.dataclass
class SlotState:
    """Host bookkeeping for one decode slot's live request."""

    request: Any
    admit_seq: int
    phase: str  # "prefill" -> "decode"
    length: int = 0          # cache positions written (valid tokens)
    prefill_done: int = 0    # prompt tokens already run
    generated: int = 0       # tokens sampled so far
    pending_token: int = 0   # sampled but not yet fed through the model
    blocks: list = dataclasses.field(default_factory=list)
    reserved: int = 0        # worst-case TOTAL blocks for this request
    # What the engine has dispatched for this slot and not yet read back
    # (ServeEngine runs one decode step ahead of its harvest, §19): the
    # final prefill chunk's first token, and decode steps past
    # ``length`` (0 or 1). Both 0 whenever the engine is at rest.
    first_unread: bool = False
    ahead: int = 0

    @property
    def budget_left(self) -> int:
        """Tokens the request may still have sampled for it, counting
        the ones that are sampled already and wait on the device."""
        return (self.request.max_new_tokens - self.generated
                - self.ahead - self.first_unread)


class Scheduler:
    def __init__(self, pool, num_slots: int, mode: str = "continuous",
                 prefix=None, role: str = "serve", tenants=None):
        if mode not in ("continuous", "static"):
            raise ValueError(f"unknown scheduler mode {mode!r}; "
                             "expected 'continuous' or 'static'")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if role not in ("serve", "prefill"):
            raise ValueError(f"unknown scheduler role {role!r}; "
                             "expected 'serve' or 'prefill'")
        self.pool = pool
        self.num_slots = num_slots
        self.mode = mode
        # name -> TenantClass. None/empty = single anonymous tenant,
        # admission stays plain FIFO (the pre-§25 behavior, bit for
        # bit). Tenants arriving WITHOUT a configured class get an
        # implicit weight-1 class: classes are scheduling policy, not
        # an ACL.
        self.tenants: dict[str, TenantClass] | None = \
            dict(tenants) if tenants else None
        self._tenant_pass: dict[str, float] = {}  # WFQ virtual passes
        self._vtime = 0.0  # virtual time = pass of the last admission
        # Optional fleet.prefix.PrefixIndex: admission consults it so
        # shared-prompt requests adopt cached blocks instead of
        # re-prefilling them.
        self.prefix = prefix
        # "serve" = round-12 behavior, prefill + decode in place.
        # "prefill" = the disagg prefill role: this scheduler only ever
        # holds prompts (reservations exclude generation tokens — the
        # finished KV ships over the edge and decodes elsewhere).
        self.role = role
        self.queue: deque = deque()
        self.slots: list[SlotState | None] = [None] * num_slots
        self._admit_seq = 0
        # Other schedulers drawing on the SAME pool (the disagg
        # degraded-prefill scheduler shares the decode pool): their
        # unallocated reservations are subtracted from this
        # scheduler's admission budget, so admitted-always-finish
        # holds jointly.
        self.peers: list = []

    # ---- queries -------------------------------------------------------

    @property
    def live(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def reserved_unallocated(self) -> int:
        """Blocks promised to live requests but not yet allocated —
        the amount the admission check must treat as already spent."""
        return sum(s.reserved - len(s.blocks)
                   for s in self.slots if s is not None)

    @property
    def pool_budget(self) -> int:
        """Blocks an admission here may draw on: the pool's
        allocatable count minus every outstanding reservation — this
        scheduler's AND its peers' on the same pool."""
        return self.pool.allocatable - self.reserved_unallocated \
            - sum(p.reserved_unallocated for p in self.peers)

    def worst_case_blocks(self, request) -> int:
        if self.role == "prefill":
            return self.pool.blocks_for(len(request.prompt))
        return self.pool.blocks_for(len(request.prompt)
                                    + request.max_new_tokens)

    def prefill_slot(self) -> int | None:
        """The slot to run a prefill chunk for this step: the OLDEST
        admitted request still prefilling (FIFO among prefills — the
        fairness rule extends inside the engine)."""
        best = None
        for i, s in enumerate(self.slots):
            if s is not None and s.phase == "prefill":
                if best is None or s.admit_seq < self.slots[best].admit_seq:
                    best = i
        return best

    def decode_slots(self) -> list[int]:
        """The slots a decode step dispatched now has a row for: in
        the decode phase with budget left once the unread tokens are
        counted, so no step is dispatched past an end the host can
        see (``max_new_tokens``)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.phase == "decode"
                and s.budget_left > 0]

    # ---- lifecycle -----------------------------------------------------

    def enqueue(self, request) -> None:
        """Validate feasibility and queue FIFO. An infeasible request
        (worst case exceeds the whole pool) is rejected HERE, loudly —
        admitting it would starve the queue forever."""
        need = self.worst_case_blocks(request)
        if need > self.pool.total_usable:
            raise ValueError(
                f"request needs up to {need} KV blocks "
                f"({len(request.prompt)} prompt + "
                f"{request.max_new_tokens} new tokens at block_size="
                f"{self.pool.block_size}) but the pool holds only "
                f"{self.pool.total_usable}")
        self.queue.append(request)

    def admit(self) -> list[int]:
        """Move queued requests into free slots under the reservation
        rule. Returns the newly filled slot indices."""
        if self.mode == "static" and self.live:
            return []  # static batching: drain fully before re-admitting
        if self.tenants is None:
            return self._admit_fifo()
        return self._admit_wfq()

    def _admit_fifo(self) -> list[int]:
        admitted = []
        for i in range(self.num_slots):
            if not self.queue or self.slots[i] is not None:
                continue
            if not self._fill_slot(i, self.queue[0]):
                break  # FIFO: never skip the head
            self.queue.popleft()
            admitted.append(i)
        return admitted

    def _admit_wfq(self) -> list[int]:
        """Weighted fair queueing over per-tenant FIFO heads (stride
        scheduling). The WFQ-selected head inherits the FIFO
        no-starvation rule: if it does not fit the pool budget,
        NOTHING else is admitted this round — retirement frees blocks
        monotonically and the tenant keeps the minimum pass until
        served, so it eventually admits. Budget-capped tenants are
        different: skipping them is the point (their own retirements
        un-cap them)."""
        admitted = []
        free = [i for i in range(self.num_slots) if self.slots[i] is None]
        capped: set[str] = set()
        while free and self.queue:
            heads: dict[str, Any] = {}
            for r in self.queue:
                heads.setdefault(tenant_of(r), r)
            cand = [t for t in heads if t not in capped]
            if not cand:
                break
            t = min(cand, key=lambda t: (
                self._tenant_pass.get(t, self._vtime),
                -self._class(t).weight, heads[t].rid))
            req = heads[t]
            cls = self._class(t)
            work = len(req.prompt) + req.max_new_tokens
            # Token budget: pass the tenant over while ITS live work
            # exceeds the cap — but never wedge a request bigger than
            # the whole budget (it admits when the tenant is idle).
            live = self.tenant_live_tokens(t)
            if cls.token_budget and live and live + work > cls.token_budget:
                capped.add(t)
                continue
            if not self._fill_slot(free[0], req):
                break  # reservation rule: stop, don't reorder past it
            self._remove_queued(req)
            admitted.append(free.pop(0))
            # Stride advance: virtual time is the served tenant's pass
            # BEFORE the increment; an idle tenant re-joining starts at
            # the current virtual time (no hoarded credit).
            t_pass = max(self._tenant_pass.get(t, 0.0), self._vtime)
            self._vtime = t_pass
            self._tenant_pass[t] = t_pass + work / cls.weight
        return admitted

    def _fill_slot(self, i: int, req) -> bool:
        """Reservation-rule check + slot fill for one request. False
        when the pool budget cannot cover the draw (the caller stops
        admitting; the request stays queued)."""
        need = self.worst_case_blocks(req)
        hit = (self.prefix.plan(req.prompt, ns=tenant_of(req))
               if self.prefix is not None else None)
        # Every draw this admission makes on (free + evictable):
        # fresh blocks (need minus cached hits, +1 for the CoW
        # copy), plus each hit whose share pins a previously
        # evictable cache entry.
        draw = need
        if hit is not None:
            draw -= len(hit.blocks)
            draw += 1 if hit.cow else 0
            draw += sum(self.pool.refcount(b) == 1
                        for b in hit.blocks)
        if draw > self.pool_budget:
            return False
        slot = SlotState(request=req, admit_seq=self._admit_seq,
                         phase="prefill", reserved=need)
        self._admit_seq += 1
        if hit is not None:
            self.prefix.share(hit)  # no-op stats on a miss
        if hit:
            slot.blocks = list(hit.blocks)
            if hit.cow:
                # The last hit block would be written in place by
                # the re-run of the final prompt token — swap in a
                # private copy and drop our share of the original.
                private = self.pool.cow(slot.blocks[-1])
                self.pool.free([slot.blocks[-1]])
                slot.blocks[-1] = private
            slot.prefill_done = hit.cached_len
            slot.length = hit.cached_len
        # Remaining prompt blocks up front (prefill scatters into
        # them this or next step); generation blocks arrive lazily.
        for _ in range(self.pool.blocks_for(len(req.prompt))
                       - len(slot.blocks)):
            slot.blocks.append(self.pool.alloc())
        self.slots[i] = slot
        return True

    def _class(self, tenant: str) -> TenantClass:
        cls = self.tenants.get(tenant) if self.tenants else None
        return cls if cls is not None else TenantClass(tenant)

    def _remove_queued(self, req) -> None:
        """Drop ``req`` from the queue by IDENTITY — dataclass
        equality would compare prompt arrays elementwise."""
        for j, r in enumerate(self.queue):
            if r is req:
                del self.queue[j]
                return
        raise RuntimeError("request vanished from the queue mid-admit")

    def tenant_live_tokens(self, tenant: str) -> int:
        """Outstanding (unretired) token work tenant ``tenant`` holds
        in live slots — the quantity its token budget caps."""
        total = 0
        for s in self.slots:
            if s is None or tenant_of(s.request) != tenant:
                continue
            total += (len(s.request.prompt) - s.prefill_done) \
                + (s.request.max_new_tokens - s.generated)
        return total

    def place(self, request, blocks, length: int,
              pending_token: int) -> int:
        """Install an externally prefilled sequence into a free slot —
        the disagg decode role's admission path. ``blocks`` are
        already allocated from THIS scheduler's pool (the edge
        adoption); the slot starts directly in the decode phase with
        its first sampled token pending. The caller checks the
        reservation rule before adopting."""
        for i in range(self.num_slots):
            if self.slots[i] is None:
                self.slots[i] = SlotState(
                    request=request, admit_seq=self._admit_seq,
                    phase="decode", length=length, prefill_done=length,
                    generated=len(request.tokens),
                    pending_token=pending_token, blocks=list(blocks),
                    reserved=self.worst_case_blocks(request))
                self._admit_seq += 1
                return i
        raise RuntimeError("place() called with no free slot — the "
                           "adopter must check capacity first")

    def ensure_block(self, idx: int) -> None:
        """Grow slot ``idx``'s table to cover writing position
        ``length`` (called before each decode step). Covered by the
        reservation, so ``alloc`` cannot fail."""
        self.ensure_blocks(idx, 1)

    def ensure_blocks(self, idx: int, width: int) -> None:
        """Grow slot ``idx``'s table to cover writing positions
        ``length .. length + width - 1`` — the speculative step's
        k+1-wide generalization of :meth:`ensure_block`. Capped at the
        request's ``prompt + max_new_tokens`` budget (positions beyond
        it scatter to the null block in-graph), so the growth never
        exceeds the admission-time worst-case ``reserved`` count and
        ``alloc`` cannot fail."""
        s = self.slots[idx]
        limit = len(s.request.prompt) + s.request.max_new_tokens
        last = min(s.length + width - 1, limit - 1)
        while last // self.pool.block_size >= len(s.blocks):
            s.blocks.append(self.pool.alloc())

    def trim_blocks(self, idx: int) -> None:
        """Free slot ``idx``'s tail blocks beyond what ``length``
        needs — the speculative KV rollback (DESIGN.md §26). A
        rejected proposal leaves over-allocated (and garbage-filled)
        tail blocks; freeing whole blocks restores
        ``free + Σ allocated == total`` with no new pool invariant.
        Keeps the block holding position ``length`` (the next write
        target), so a kept block's garbage tail is causally masked.

        Tiered pools (§27): the kept frontier block must be PROMOTED
        before the tail is trimmed. A deep rollback can land the write
        frontier in a block whose pages were demoted or spilled while
        the speculative window raced ahead; the next decode step
        scatters into that block's hot slot, so leaving it cold would
        silently drop the accepted prefix's most recent tokens."""
        s = self.slots[idx]
        keep = s.length // self.pool.block_size + 1
        if len(s.blocks) > keep:
            if self.pool.tiers > 1:
                self.pool.ensure_hot([s.blocks[keep - 1]])
            self.pool.free(s.blocks[keep:])
            del s.blocks[keep:]

    def retire(self, idx: int) -> None:
        """Free slot ``idx``'s blocks and reservation."""
        s = self.slots[idx]
        self.pool.free(s.blocks)
        self.slots[idx] = None

    def release(self, idx: int) -> SlotState:
        """Clear slot ``idx`` WITHOUT freeing its blocks — ownership
        transfer, not retirement. The caller must hand the returned
        state's blocks to another scheduler on the SAME pool (the
        degraded-prefill -> decode handover) or free them itself."""
        s = self.slots[idx]
        self.slots[idx] = None
        return s

    def accounting_ok(self) -> bool:
        """The page-pool invariant (§19, extended by §21 refcounts),
        checkable at any step: every holder the scheduler knows about
        — live block tables plus the prefix index — accounts for every
        refcount, and ``free + Σ unique-allocated == total usable``."""
        holders = [s.blocks for s in self.slots if s is not None]
        if self.prefix is not None:
            holders.append(self.prefix.held_blocks())
        return self.pool.refcount_ok(holders)
