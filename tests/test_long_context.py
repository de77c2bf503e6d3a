"""Long-context serving (tpu_ddp/serve/long_context.py, DESIGN.md §27):
the tiered KV pool's residency state machine, the tier-accounting
identity fuzz (satellite of §27), the promote-before-trim rollback fix,
tiered-engine exactness against the single-pool oracle, and
context-parallel chunked prefill parity on the forced 8-device host
platform.

Exactness strategy: the bf16 hot tier with the bf16 cold codec is
LOSSLESS (parallel/compress.py stores a plain downcast with unit
scales), so a tiers=3 engine under HBM pressure must emit the EXACT
token stream of a tiers=1 bf16 engine — demote/spill/promote traffic
changes where bytes live, never what they are. The int8 codec is
semantic (rounded re-reads) and is exercised for liveness + accounting
only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.parallel.mesh import make_mesh, replicated_sharding
from tpu_ddp.serve import (
    PagedKVPool,
    Request,
    Scheduler,
    ServeEngine,
    make_long_prompt_workload,
)

# The shared fast-tier cache geometry (tests/test_serve.py): tiered
# engines reuse the same logical pool so the scheduler math is
# identical; only hbm_blocks/cold_blocks vary the residency pressure.
GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    return make_transformer("TransformerLM-tiny", max_seq_len=64,
                            compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def _prompt(L, seed=0):
    return np.random.default_rng(seed).integers(0, 1024, size=L,
                                                dtype=np.int64)


def _stream(model, params, cases, **kw):
    """Greedy streams for ``cases = [(prompt_len, max_new), ...]``
    through one engine configuration."""
    cfg = dict(GEOM)
    cfg.update(kw)
    eng = ServeEngine(model, params, **cfg)
    reqs = [eng.submit(_prompt(L, seed=100 + i), n)
            for i, (L, n) in enumerate(cases)]
    eng.run()
    assert all(r.done and not r.cancelled for r in reqs)
    assert eng.pool.free_count == eng.pool.total_usable
    assert eng.sched.accounting_ok()
    return [np.asarray(r.tokens) for r in reqs]


# ---------------------------------------------------------------------------
# Tiered pool mechanics
# ---------------------------------------------------------------------------

class TestTieredPool:
    def test_tiers1_is_identity(self, model):
        # The default pool is the round-12 layout bit-for-bit: logical
        # id == hot slot, no cold buffers, trivial tier accounting.
        pool = PagedKVPool(model, 9, 8)
        b = pool.alloc()
        assert pool.hot_slot(b) == b
        assert pool.cold_k is None
        assert pool.tier_of(b) == "hot"
        assert pool.tier_accounting_ok()
        hot, cold = pool.slot_tables([b], 4)
        assert hot[0] == b and not cold.any()

    def test_geometry_validation(self, model):
        with pytest.raises(ValueError, match="tiers"):
            PagedKVPool(model, 9, 8, tiers=4)
        with pytest.raises(ValueError, match="cold_dtype"):
            PagedKVPool(model, 9, 8, tiers=2, cold_dtype="fp4")
        with pytest.raises(ValueError, match="hbm_blocks"):
            PagedKVPool(model, 9, 8, tiers=2, hbm_blocks=1)
        with pytest.raises(ValueError, match="cold_blocks"):
            PagedKVPool(model, 9, 8, tiers=2, cold_blocks=1)

    def test_lifecycle_fresh_to_spill_and_back(self, model):
        # FREE -> FRESH -> HOT -> COLD -> SPILL -> COLD -> HOT, driven
        # purely by residency pressure (hot_usable=2, cold usable=2,
        # tiers=3 so the overflow lands on the host).
        pool = PagedKVPool(model, 9, 8, tiers=3, hbm_blocks=3,
                           cold_blocks=3)
        blocks = [pool.alloc() for _ in range(6)]
        assert all(pool.tier_of(b) == "fresh" for b in blocks)
        for b in blocks:
            pool.ensure_hot([b])
        counts = pool.tier_counts()
        assert counts["hot"] == 2 and counts["cold"] == 2
        assert counts["spill"] == 2
        assert pool.tier_accounting_ok()
        spilled = [b for b in blocks if pool.tier_of(b) == "spill"]
        # slot_tables refuses spilled pages: residency is an explicit
        # precondition of every step program, never an implicit fetch.
        with pytest.raises(RuntimeError, match="spill"):
            pool.slot_tables([spilled[0]], 4)
        pool.ensure_device(spilled)
        assert all(pool.tier_of(b) == "cold" for b in spilled)
        pool.ensure_hot([spilled[0]])
        assert pool.tier_of(spilled[0]) == "hot"
        assert pool.tier_accounting_ok()
        pool.free(blocks)
        assert pool.tier_counts()["hot"] == 0
        assert pool.free_count == pool.total_usable
        assert pool.tier_accounting_ok()

    def test_overcommitted_ensure_hot_is_loud(self, model):
        pool = PagedKVPool(model, 9, 8, tiers=3, hbm_blocks=3,
                           cold_blocks=3)
        blocks = [pool.alloc() for _ in range(3)]
        with pytest.raises(RuntimeError, match="hot"):
            pool.ensure_hot(blocks)  # 3 targets > hot_usable == 2

    def test_tiers2_has_no_spill_tier(self, model):
        # tiers=2 keeps cold pages in HBM only: once hot+cold is full,
        # further residency demands must fail loudly, not silently
        # drop pages.
        pool = PagedKVPool(model, 9, 8, tiers=2, hbm_blocks=3,
                           cold_blocks=3)
        blocks = [pool.alloc() for _ in range(5)]
        for b in blocks[:4]:
            pool.ensure_hot([b])
        with pytest.raises(RuntimeError, match="cold"):
            pool.ensure_hot([blocks[4]])

    def test_bf16_spill_roundtrip_is_lossless(self, model):
        # The parity-bearing tier: bf16 hot + bf16 cold stores a plain
        # downcast (unit scales), so HOT -> COLD -> SPILL -> HOT
        # returns the exact bytes.
        pool = PagedKVPool(model, 9, 8, "bf16", tiers=3, hbm_blocks=3,
                           cold_blocks=3, cold_dtype="bf16")
        b = pool.alloc()
        pool.ensure_hot([b])
        rng = np.random.default_rng(0)
        page = jnp.asarray(rng.standard_normal(
            pool.k[:, 0].shape), jnp.bfloat16)
        s = pool.hot_slot(b)
        pool.k = pool.k.at[:, s].set(page)
        pool.v = pool.v.at[:, s].set(-page)
        others = [pool.alloc() for _ in range(4)]
        for o in others:          # evict b all the way to the host
            pool.ensure_hot([o])
        assert pool.tier_of(b) == "spill"
        pool.ensure_device([b])
        pool.ensure_hot([b])
        kb, vb = pool.page_arrays([b])
        np.testing.assert_array_equal(np.asarray(kb[:, 0], np.float32),
                                      np.asarray(page, np.float32))
        np.testing.assert_array_equal(np.asarray(vb[:, 0], np.float32),
                                      np.asarray(-page, np.float32))

    def test_int8_roundtrip_is_close(self, model):
        pool = PagedKVPool(model, 9, 8, tiers=3, hbm_blocks=3,
                           cold_blocks=3, cold_dtype="int8")
        b = pool.alloc()
        pool.ensure_hot([b])
        rng = np.random.default_rng(1)
        page = jnp.asarray(rng.standard_normal(pool.k[:, 0].shape),
                           jnp.float32)
        pool.k = pool.k.at[:, pool.hot_slot(b)].set(page)
        others = [pool.alloc() for _ in range(4)]
        for o in others:
            pool.ensure_hot([o])
        assert pool.tier_of(b) == "spill"
        pool.ensure_hot([b])
        kb, _ = pool.page_arrays([b])
        # Per-token-row scale = max|x|/127: worst-case rounding error
        # is scale/2, and |x| <= ~5 sigma here.
        np.testing.assert_allclose(np.asarray(kb[:, 0]),
                                   np.asarray(page), atol=0.05)

    def test_cow_of_spilled_source(self, model):
        pool = PagedKVPool(model, 17, 8, "bf16", tiers=3, hbm_blocks=4,
                           cold_blocks=4, cold_dtype="bf16")
        b = pool.alloc()
        pool.ensure_hot([b])
        page = jnp.ones(pool.k[:, 0].shape, jnp.bfloat16)
        pool.k = pool.k.at[:, pool.hot_slot(b)].set(page)
        for _ in range(6):        # push b off the device entirely
            pool.ensure_hot([pool.alloc()])
        assert pool.tier_of(b) == "spill"
        new = pool.cow(b)
        assert pool.tier_of(new) == "hot" and pool.tier_of(b) == "hot"
        kb, _ = pool.page_arrays([new])
        np.testing.assert_array_equal(np.asarray(kb[:, 0], np.float32),
                                      np.ones(kb[:, 0].shape, np.float32))

    def test_scrub_reaches_every_tier(self, model):
        pool = PagedKVPool(model, 9, 8, tiers=3, hbm_blocks=3,
                           cold_blocks=3)
        blocks = [pool.alloc() for _ in range(6)]
        for b in blocks:
            pool.ensure_hot([b])
            s = pool.hot_slot(b)
            pool.k = pool.k.at[:, s].set(jnp.nan)
            pool.v = pool.v.at[:, s].set(jnp.nan)
        # Poison now lives in hot slots, cold pages and host spill.
        pool.scrub(blocks)
        for b in blocks:          # one at a time: device holds 4 pages
            pool.ensure_device([b])
            pool.ensure_hot([b])
            kb, vb = pool.page_arrays([b])
            assert not np.isnan(np.asarray(kb, np.float32)).any()
            assert not np.isnan(np.asarray(vb, np.float32)).any()


# ---------------------------------------------------------------------------
# Satellite: the tier-accounting identity, fuzzed
# ---------------------------------------------------------------------------

class TestTierAccountingFuzz:
    @pytest.mark.parametrize("tiers,seed", [(2, 0), (3, 1), (3, 2)])
    def test_identity_holds_under_random_ops(self, model, tiers, seed):
        """``hot_free + hot_resident == hot usable`` (and the cold
        analog) through a random storm of alloc / free / incref / cow /
        scrub / spill / promote, with the full refcount identity
        checked via ``refcount_ok`` after EVERY op. tiers=2 runs the
        same storm with no spill tier (residency demands that overflow
        hot+cold raise instead)."""
        cold = 40 if tiers == 2 else 6
        pool = PagedKVPool(model, 33, 8, tiers=tiers, hbm_blocks=5,
                           cold_blocks=cold)
        rng = np.random.default_rng(seed)
        holders: list[list[int]] = []

        def live():
            return sorted({b for h in holders for b in h})

        for _ in range(250):
            op = rng.integers(0, 7)
            if op == 0 and pool.free_count:
                holders.append([pool.alloc()])
            elif op == 1 and holders:
                dead = holders.pop(rng.integers(len(holders)))
                pool.free(dead)
            elif op == 2 and live():
                b = int(rng.choice(live()))
                pool.incref([b])
                holders.append([b])
            elif op == 3 and live() and pool.free_count:
                b = int(rng.choice(live()))
                try:
                    holders.append([pool.cow(b)])
                except RuntimeError:
                    pass          # tiers=2 device full: loud, not wrong
            elif op == 4 and live():
                n = int(rng.integers(1, pool.hot_usable + 1))
                pick = list(rng.choice(live(), size=min(n, len(live())),
                                       replace=False))
                try:
                    pool.ensure_hot([int(b) for b in pick])
                except RuntimeError:
                    pass
            elif op == 5 and live():
                pick = list(rng.choice(live(),
                                       size=min(3, len(live())),
                                       replace=False))
                pool.ensure_device([int(b) for b in pick])
            elif op == 6 and live():
                pool.scrub([int(rng.choice(live()))])
            assert pool.refcount_ok(holders), \
                f"accounting identity broken after op {op}"
        for h in holders:
            pool.free(h)
        assert pool.free_count == pool.total_usable
        assert pool.tier_counts()["spill"] == 0
        assert pool.refcount_ok([])


# ---------------------------------------------------------------------------
# Satellite: promote-before-trim (the speculative rollback fix)
# ---------------------------------------------------------------------------

class TestPromoteBeforeTrim:
    def test_trim_promotes_the_kept_frontier(self, model):
        """A deep rollback lands the write frontier in a block that
        residency pressure demoted while the speculative window raced
        ahead. ``trim_blocks`` must promote that block BEFORE freeing
        the tail — the next decode step scatters into its hot slot."""
        pool = PagedKVPool(model, 33, 8, tiers=3, hbm_blocks=4,
                           cold_blocks=33)
        sched = Scheduler(pool, num_slots=1)
        sched.enqueue(Request(rid=0, prompt=np.zeros(8, np.int32),
                              max_new_tokens=40))
        idx = sched.admit()[0]
        s = sched.slots[idx]
        sched.ensure_blocks(idx, 32)          # speculative over-growth
        assert len(s.blocks) > pool.hot_usable
        fi = s.length // pool.block_size
        frontier = s.blocks[fi]
        # Pressure from the speculative tail pushes the frontier off
        # the device: hot_usable == 3, four distinct blocks demand
        # residency, and the frontier is the LRU-coldest.
        pool.ensure_hot([frontier])
        for b in s.blocks[:fi] + s.blocks[fi + 1:]:
            pool.ensure_hot([b])
        assert pool.tier_of(frontier) != "hot"
        sched.trim_blocks(idx)
        assert pool.tier_of(frontier) == "hot"
        assert len(s.blocks) == s.length // pool.block_size + 1
        assert pool.refcount_ok([s.blocks])


# ---------------------------------------------------------------------------
# Tiered engine exactness + liveness
# ---------------------------------------------------------------------------

class TestTieredEngine:
    def test_bf16_tiered_stream_matches_single_pool(self, model,
                                                    params):
        """The §27 exactness bar: tiers=3 under real pressure (hot
        tier holds 5 of up to 32 live pages; spill exercised) emits
        the EXACT stream of the tiers=1 bf16 oracle across a mixed
        continuous batch."""
        cases = [(3, 6), (11, 6), (20, 4), (9, 12)]
        want = _stream(model, params, cases, cache_dtype="bf16")
        got = _stream(model, params, cases, cache_dtype="bf16",
                      kv_tiers=3, kv_cold_dtype="bf16", hbm_blocks=6,
                      cold_blocks=33)
        for i, (w, g) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(
                g, w, err_msg=f"request {i} diverged under tiering")

    # Speculation x tiering: the fused step's all-hot slot translation
    # (three engine builds -> slow tier).
    @pytest.mark.slow
    def test_fused_spec_all_hot_translation(self, model, params):
        # Fused drafts run the round-17 program against HOT SLOT ids:
        # exact only when whole tables fit hot. Streams must match the
        # tiers=1 engine running the same fused draft.
        cases = [(5, 8), (9, 6)]
        want = _stream(model, params, cases, cache_dtype="bf16",
                       num_slots=2, spec_k=2, spec_draft="self-1")
        got = _stream(model, params, cases, cache_dtype="bf16",
                      kv_tiers=3, kv_cold_dtype="bf16", hbm_blocks=33,
                      cold_blocks=33, num_slots=2, spec_k=2,
                      spec_draft="self-1")
        for w, g in zip(want, got):
            np.testing.assert_array_equal(g, w)

    def test_int8_cold_tier_liveness(self, model, params):
        # The semantic codec: full-length generations through the same
        # programs, accounting clean; no token-level claim.
        eng = ServeEngine(model, params, **GEOM, kv_tiers=3,
                          kv_cold_dtype="int8", hbm_blocks=6,
                          cold_blocks=33)
        reqs = [eng.submit(_prompt(L, seed=40 + i), n)
                for i, (L, n) in enumerate([(10, 6), (17, 5)])]
        eng.run()
        assert all(r.done and len(r.tokens) == n
                   for r, (_, n) in zip(reqs, [(10, 6), (17, 5)]))
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.pool.tier_accounting_ok()

    def test_long_prompt_workload_exceeds_hot_capacity(self, model,
                                                       params):
        # The tentpole claim in miniature: a prompt needing 6 blocks
        # served with 3 hot pages — total context bounded by the
        # logical pool, hot context by hbm_blocks.
        spec = make_long_prompt_workload(1, model.vocab_size, seed=7,
                                         prompt_len=44, max_new=(4, 5))[0]
        eng = ServeEngine(model, params, num_slots=1, block_size=8,
                          prefill_chunk=8, kv_tiers=3,
                          kv_cold_dtype="int8", hbm_blocks=4,
                          cold_blocks=9)
        req = eng.submit(spec.prompt, spec.max_new_tokens)
        eng.run()
        assert req.done and len(req.tokens) == spec.max_new_tokens
        assert eng.pool.tier_accounting_ok()


# ---------------------------------------------------------------------------
# Context-parallel chunked prefill
# ---------------------------------------------------------------------------

class TestCPPrefill:
    @pytest.mark.parametrize("mode", ["ring", "ulysses"])
    def test_stream_matches_single_rank(self, model, params, mode):
        """Sharding each prefill chunk's query rows over sp ranks must
        not change a single emitted token. 29-token prompt: three full
        chunks plus a ragged 5-token tail (partial final chunk, sample
        position inside the chunk)."""
        sp = 4
        mesh = make_mesh(jax.devices()[:sp], dp=1, sp=sp)
        rp = jax.device_put(params, replicated_sharding(mesh))
        cases = [(29, 6), (8, 5)]
        want = _stream(model, params, cases)
        got = _stream(model, rp, cases, cp_prefill=mode, mesh=mesh)
        for i, (w, g) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(
                g, w, err_msg=f"request {i} diverged under cp={mode}")

    def test_rejected_combinations(self, model, params):
        sp = 2
        mesh = make_mesh(jax.devices()[:sp], dp=1, sp=sp)
        rp = jax.device_put(params, replicated_sharding(mesh))
        with pytest.raises(ValueError, match="single-tier"):
            ServeEngine(model, rp, **GEOM, cp_prefill="ring",
                        mesh=mesh, kv_tiers=2)
        with pytest.raises(ValueError, match="sp"):
            ServeEngine(model, params, **GEOM, cp_prefill="ring")
        with pytest.raises(ValueError, match="divide"):
            ServeEngine(model, rp, num_slots=4, block_size=8,
                        prefill_chunk=9, cp_prefill="ring", mesh=mesh)
        with pytest.raises(ValueError, match="cp_prefill"):
            ServeEngine(model, params, **GEOM, cp_prefill="dp")


# ---------------------------------------------------------------------------
# Knob surfaces
# ---------------------------------------------------------------------------

class TestKnobs:
    def test_env_defaults_flow_into_engine(self, model, params,
                                           monkeypatch):
        monkeypatch.setenv("TPU_DDP_KV_TIERS", "3")
        monkeypatch.setenv("TPU_DDP_KV_COLD_DTYPE", "bf16")
        eng = ServeEngine(model, params, **GEOM)
        assert eng.kv_tiers == 3
        assert eng.kv_cold_dtype == "bf16"
        assert eng.pool.tiers == 3

    @pytest.mark.parametrize("env,junk", [
        ("TPU_DDP_KV_TIERS", "0"),
        ("TPU_DDP_KV_TIERS", "many"),
        ("TPU_DDP_KV_COLD_DTYPE", "fp8"),
        ("TPU_DDP_CP_PREFILL", "dp"),
    ])
    def test_junk_env_rejected(self, env, junk, monkeypatch):
        from tpu_ddp.utils.config import TrainConfig
        monkeypatch.setenv(env, junk)
        with pytest.raises(ValueError, match=env):
            TrainConfig()

    def test_long_prompt_workload_shape(self):
        w = make_long_prompt_workload(5, 1024, seed=3, prompt_len=256,
                                      max_new=(4, 9))
        assert len(w) == 5
        assert all(len(s.prompt) == 256 for s in w)
        assert all(4 <= s.max_new_tokens < 9 for s in w)
        again = make_long_prompt_workload(5, 1024, seed=3,
                                          prompt_len=256, max_new=(4, 9))
        for a, b in zip(w, again):
            np.testing.assert_array_equal(a.prompt, b.prompt)
