"""The serving fleet (tpu_ddp/fleet/): refcounted prefix caching over
the paged pool, prefill/decode disaggregation over the KV edge, and the
multi-replica router (docs/DESIGN.md §21).

The acceptance bar everything here leans on is BITWISE TOKEN PARITY:
same seed and request set in, identical tokens out — whether requests
run through one engine, a prefix-cached engine, a disaggregated
prefill/decode pair (``kv_wire="none"``), or a routed fleet. Sampling
is stateless-keyed by (seed, position) and the decode math has exactly
one implementation (``serve/engine.decode_bank``), so any divergence is
a real bug in block bookkeeping, not float noise.

Geometry matches tests/test_serve.py (block_size=8, num_slots=4 at
max_seq_len=64), so the single-engine step programs are shared; the
fused adopt+decode program adds one compile per distinct transfer
block-count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.fleet import DisaggEngine, KVEdge, PrefixIndex, Router
from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.serve import (
    PagedKVPool,
    ServeEngine,
    make_shared_prefix_workload,
    run_load,
)

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


def _serve_all(engine, cases, seed0=0):
    """Submit (prompt_seed, L, n, temp) cases, run to idle, return the
    per-request token lists."""
    hs = [engine.submit(_prompt(L, seed=ps), n, temperature=t, seed=i)
          for i, (ps, L, n, t) in enumerate(cases, start=seed0)]
    engine.run()
    assert all(h.done for h in hs)
    return [h.tokens for h in hs]


MIXED = [(0, 5, 6, 0.0), (1, 9, 5, 0.0), (2, 12, 4, 0.7),
         (3, 8, 6, 1.0)]


class TestRefcounts:
    def test_share_free_lifecycle_and_identity(self, model):
        pool = PagedKVPool(model, num_blocks=6, block_size=8)
        b = pool.alloc()
        assert pool.refcount(b) == 1
        pool.incref([b])
        pool.incref([b])
        assert pool.refcount(b) == 3
        pool.free([b])                   # decref, still held
        pool.free([b])
        assert pool.refcount(b) == 1 and pool.free_count == 4
        pool.free([b])                   # last holder: page returns
        assert pool.refcount(b) == 0 and pool.free_count == 5
        # §21 identity: free + unique-allocated == total usable.
        a, c = pool.alloc(), pool.alloc()
        pool.incref([a])
        assert pool.refcount_ok([[a, c], [a]])
        assert not pool.refcount_ok([[a, c]])     # missing a holder
        assert not pool.refcount_ok([[a, c], [a], [c]])  # phantom

    def test_refcount_never_negative(self, model):
        pool = PagedKVPool(model, num_blocks=4, block_size=8)
        b = pool.alloc()
        pool.free([b])
        with pytest.raises(ValueError, match="double free"):
            pool.free([b])
        assert pool.refcount(b) == 0     # clamped by the raise
        with pytest.raises(ValueError, match="unallocated"):
            pool.incref([b])             # can't resurrect a free page
        with pytest.raises(ValueError, match="null block"):
            pool.incref([PagedKVPool.NULL_BLOCK])

    def test_cow_copies_content_into_private_block(self, model):
        pool = PagedKVPool(model, num_blocks=4, block_size=8)
        b = pool.alloc()
        pool.commit(pool.k.at[:, b].set(7.0), pool.v.at[:, b].set(3.0))
        pool.incref([b])
        c = pool.cow(b)
        assert c != b and pool.refcount(c) == 1
        np.testing.assert_array_equal(np.asarray(pool.k[:, c]),
                                      np.asarray(pool.k[:, b]))
        np.testing.assert_array_equal(np.asarray(pool.v[:, c]),
                                      np.asarray(pool.v[:, b]))
        # Writing the copy leaves the shared original untouched.
        pool.commit(pool.k.at[:, c].set(9.0), pool.v)
        assert float(pool.k[0, b, 0, 0]) == 7.0


class TestPrefixIndex:
    def test_chain_keys_are_exact_and_plan_is_pure(self, model):
        pool = PagedKVPool(model, num_blocks=8, block_size=8)
        idx = PrefixIndex(pool)
        p = _prompt(16, seed=1)
        blocks = [pool.alloc(), pool.alloc()]
        idx.register(p, blocks)
        assert idx.stats()["entries"] == 2
        hit = idx.plan(p)
        assert hit.blocks == blocks
        assert hit.cached_len == 15      # final token always re-runs
        assert hit.cow                   # block-aligned full match
        # One shared token-block prefix, divergent second block.
        q = np.concatenate([p[:8], _prompt(8, seed=2)])
        h2 = idx.plan(q)
        assert h2.blocks == blocks[:1] and h2.cached_len == 8
        assert not h2.cow
        # A token flip in the FIRST block kills the whole chain.
        r = p.copy()
        r[0] = (r[0] + 1) % 1024
        assert not idx.plan(r)
        # plan() took no refcounts and no stats.
        assert pool.refcount(blocks[0]) == 2  # slot + index only
        assert idx.lookups == 0

    def test_reclaim_is_lru_leaf_first_with_cascade(self, model):
        pool = PagedKVPool(model, num_blocks=8, block_size=8)
        idx = PrefixIndex(pool)
        pa = _prompt(16, seed=3)
        ba = [pool.alloc(), pool.alloc()]
        idx.register(pa, ba)
        pool.free(ba)                    # index is now the only holder
        assert idx.evictable_count == 1  # leaf only (conservative)
        assert pool.allocatable == pool.free_count + 1
        # A dry pool reclaims THROUGH the index: leaf, then its parent.
        got = [pool.alloc() for _ in range(pool.free_count + 2)]
        assert len(set(got)) == len(got)
        assert idx.stats()["entries"] == 0 and idx.evicted == 2
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc()

    def test_shared_prompt_parity_and_stats(self, model, params):
        """N requests sharing a 16-token system prompt through a
        prefix-cached engine: tokens bitwise-equal the uncached
        engine's, the shared blocks are prefilled ONCE, and the
        accounting identity holds after the drill."""
        system = _prompt(16, seed=4)
        tails = [_prompt(4, seed=10 + i) for i in range(3)]
        prompts = [np.concatenate([system, t]) for t in tails]
        plain = ServeEngine(model, params, **GEOM)
        cached = ServeEngine(model, params, prefix_cache=True, **GEOM)
        want, got = [], []
        for i, p in enumerate(prompts):
            a = plain.submit(p, 5, seed=i)
            plain.run()
            b = cached.submit(p, 5, seed=i)
            cached.run()
            want.append(a.tokens)
            got.append(b.tokens)
        assert got == want
        st = cached.prefix.stats()
        assert st["hit_requests"] == 2          # first pays, rest hit
        assert st["tokens_saved"] == 2 * 16
        assert cached.sched.accounting_ok()

    def test_cow_divergence_is_bitwise_private(self, model, params):
        """Two IDENTICAL block-aligned prompts: the second adopts every
        prompt block and re-runs only the final token into a CoW copy.
        Its tokens must equal the uncached engine's bitwise, and the
        original cached block must stay pristine for a third hit."""
        p = _prompt(16, seed=5)
        plain = ServeEngine(model, params, **GEOM)
        cached = ServeEngine(model, params, prefix_cache=True, **GEOM)
        want = []
        for i in range(3):
            h = plain.submit(p, 5, temperature=0.5, seed=i)
            plain.run()
            want.append(h.tokens)
        got = []
        for i in range(3):
            h = cached.submit(p, 5, temperature=0.5, seed=i)
            cached.run()
            got.append(h.tokens)
        assert got == want
        st = cached.prefix.stats()
        assert st["hit_requests"] == 2
        assert cached.sched.accounting_ok()


class TestDisagg:
    def test_bitwise_parity_with_single_engine(self, model, params):
        single = ServeEngine(model, params, **GEOM)
        fleet = DisaggEngine(model, params, kv_wire="none", **GEOM)
        assert _serve_all(fleet, MIXED) == _serve_all(single, MIXED)
        # Both roles drain completely.
        assert fleet.pool.free_count == fleet.pool.total_usable
        assert fleet.prefill_pool.free_count \
            == fleet.prefill_pool.total_usable
        assert fleet.accounting_ok()
        assert fleet.edge.stats()["sent"] \
            == fleet.edge.stats()["delivered"] == len(MIXED)

    def test_parity_with_prefix_cache_on(self, model, params):
        system = _prompt(16, seed=6)
        prompts = [np.concatenate([system, _prompt(3, seed=20 + i)])
                   for i in range(3)]
        single = ServeEngine(model, params, **GEOM)
        fleet = DisaggEngine(model, params, kv_wire="none",
                             prefix_cache=True, **GEOM)
        want, got = [], []
        for i, p in enumerate(prompts):
            a = single.submit(p, 4, seed=i)
            single.run()
            b = fleet.submit(p, 4, seed=i)
            fleet.run()
            want.append(a.tokens)
            got.append(b.tokens)
        assert got == want
        assert fleet.prefix.stats()["hit_requests"] == 2
        assert fleet.accounting_ok()

    @pytest.mark.parametrize("wire,min_ratio", [("bf16", 1.9),
                                                ("int8", 3.0)])
    def test_lossy_wires_complete_and_compress(self, model, params,
                                               wire, min_ratio):
        fleet = DisaggEngine(model, params, kv_wire=wire, **GEOM)
        hs = [fleet.submit(_prompt(9, seed=30 + i), 5)
              for i in range(2)]
        fleet.run()
        assert all(h.done and len(h.tokens) == 5 for h in hs)
        st = fleet.edge.stats()
        assert st["ratio"] >= min_ratio  # honest byte accounting
        assert fleet.pool.free_count == fleet.pool.total_usable

    def test_wire_validation(self):
        with pytest.raises(ValueError, match="kv_wire"):
            KVEdge("fp4")

    def test_transfer_lands_behind_decode_compute(self, model, params):
        """The overlap claim, checked on compiled HLO: the fused
        adopt+decode program's landing scatters have NO heavy ancestor
        (the transfer can start at step begin) and heavy decode ops
        outside their cones to hide behind."""
        from tpu_ddp.utils.hlo_comm import (
            assert_transfer_overlap,
            update_overlap_report,
        )
        fleet = DisaggEngine(model, params, **GEOM)
        rep = assert_transfer_overlap(fleet.adopt_decode_hlo(2))
        assert rep["n_updates"] >= 2     # k and v landings
        assert all(u["n_heavy_ancestors"] == 0 for u in rep["updates"])
        # Negative control: the same math with the adoption applied
        # AFTER the decode bank serializes the landing behind every
        # heavy op feeding the pool — the analysis must say NO.
        import functools

        from tpu_ddp.serve.engine import decode_bank

        def bad_step(params, pool_k, pool_v, adopt_ids, adopt_k,
                     adopt_v, tables, lengths, last_tokens, temps,
                     seeds):
            k, v, toks, lps, _bad = decode_bank(
                model, fleet.block_size, params, pool_k, pool_v, tables,
                lengths, last_tokens, temps, seeds)
            k = k.at[:, adopt_ids].set(adopt_k.astype(k.dtype))
            v = v.at[:, adopt_ids].set(adopt_v.astype(v.dtype))
            return k, v, toks, lps

        fn = jax.jit(bad_step, donate_argnums=(1, 2))
        sds = jax.ShapeDtypeStruct
        spec = jax.tree.map(lambda x: sds(jnp.shape(x),
                                          jnp.result_type(x)),
                            fleet.params)
        S, BPS = fleet.num_slots, fleet.blocks_per_seq
        pk = sds(fleet.pool.k.shape, fleet.pool.k.dtype)
        pay = sds((model.num_layers, 2, fleet.block_size,
                   model.kv_heads * model.head_dim), jnp.float32)
        i32 = functools.partial(sds, dtype=jnp.int32)
        bad = fn.lower(spec, pk, pk, i32((2,)), pay, pay,
                       i32((S, BPS)), i32((S,)), i32((S,)),
                       sds((S,), jnp.float32),
                       i32((S,))).compile().as_text()
        brep = update_overlap_report(bad)
        assert not brep["overlapped"]
        assert all(u["n_heavy_ancestors"] > 0 for u in brep["updates"])
        with pytest.raises(AssertionError, match="not overlappable"):
            assert_transfer_overlap(bad)


class TestRouter:
    def test_validation_and_least_loaded_balance(self, model, params):
        with pytest.raises(ValueError, match="at least one"):
            Router([])
        with pytest.raises(ValueError, match="policy"):
            Router([ServeEngine(model, params, **GEOM)], policy="rr")
        r = Router([ServeEngine(model, params, **GEOM)
                    for _ in range(2)], policy="least-loaded")
        for i in range(6):
            r.submit(_prompt(6, seed=40 + i), 4, seed=i)
        assert r.routed == [3, 3]        # alternating under equal load
        r.run()
        assert r.accounting_ok() and r.outstanding() == 0

    def test_routed_fleet_matches_single_engine_tokens(self, model,
                                                       params):
        single = ServeEngine(model, params, **GEOM)
        want = _serve_all(single, MIXED)
        r = Router([ServeEngine(model, params, prefix_cache=True,
                                **GEOM) for _ in range(2)],
                   policy="prefix-affinity")
        got = _serve_all(r, MIXED)
        assert got == want               # parity survives routing

    def test_prefix_affinity_beats_least_loaded_hit_rate(self, model,
                                                         params):
        """The policy's reason to exist: shared-prompt traffic piled
        onto the replica that already paid the prefill. Deterministic
        pacing (placement, not timing, is under test): one warm-up
        request drained alone, then PAIRS submitted together so
        least-loaded must spread each pair — its second stream pays
        the shared prefill again on the cold replica."""
        def fleet(policy):
            return Router([ServeEngine(model, params,
                                       prefix_cache=True, **GEOM)
                           for _ in range(2)], policy=policy)

        def hit_rate(router, specs):
            router.submit(specs[0].prompt, specs[0].max_new_tokens,
                          seed=specs[0].seed)
            router.run()
            for a, b in zip(specs[1::2], specs[2::2]):
                for sp in (a, b):        # concurrent pair
                    router.submit(sp.prompt, sp.max_new_tokens,
                                  seed=sp.seed)
                router.run()
            st = [rep["prefix"] for rep in
                  router.stats()["replicas"]]
            return (sum(s["hit_requests"] for s in st)
                    / sum(s["lookups"] for s in st))

        specs = make_shared_prefix_workload(9, model.vocab_size,
                                            seed=7, prefix_len=16)
        aff, ll = fleet("prefix-affinity"), fleet("least-loaded")
        r_aff, r_ll = hit_rate(aff, specs), hit_rate(ll, specs)
        assert r_aff == 8 / 9            # one cold miss total
        assert r_ll == 7 / 9             # one cold miss PER replica
        assert r_aff > r_ll
        assert aff.affinity_hits == 8
        # Affinity concentrated the stream; least-loaded split it.
        assert sorted(aff.routed) == [0, 9]
        assert sorted(ll.routed) == [4, 5]

    def test_affinity_slack_caps_hot_replica_pileup(self, model,
                                                    params):
        r = Router([ServeEngine(model, params, prefix_cache=True,
                                **GEOM) for _ in range(2)],
                   policy="prefix-affinity", affinity_slack=0)
        p = _prompt(20, seed=8)
        r.submit(p, 8)
        r.run()                          # replica 0 caches the prompt
        r.submit(p, 8)                   # backlog 0 vs 0: affinity OK
        assert r.routed[0] == 2
        # Replica 0 now owes work; slack 0 forces the next one over.
        i = r.pick(p)
        assert i == 1
        r.run()

    @pytest.mark.slow  # wall-clock fleet drill (~30-60 s)
    def test_two_replica_fleet_no_leak_drill(self, model, params):
        """The §21 acceptance drill: a 2-replica disagg+prefix fleet
        under a shared-prefix open-system load, accounting checked at
        the end on every pool in the fleet — nothing leaks, nothing
        double-frees, and the run produces full-length generations."""
        replicas = [DisaggEngine(model, params, kv_wire="bf16",
                                 prefix_cache=True, **GEOM)
                    for _ in range(2)]
        router = Router(replicas, policy="prefix-affinity")
        specs = make_shared_prefix_workload(
            40, model.vocab_size, seed=9, prefix_len=16,
            tail_len=(2, 9), max_new=(3, 9))
        m = run_load(router, specs, rate=100.0, seed=9)
        assert m["n_requests"] == 40
        assert m["total_tokens"] == sum(s.max_new_tokens
                                        for s in specs)
        assert m["tpot_p99_ms"] is not None
        assert router.accounting_ok()
        for rep in replicas:
            assert rep.pool.free_count == rep.pool.total_usable
            held = len(rep.prefix.held_blocks())
            assert rep.prefill_pool.free_count + held \
                == rep.prefill_pool.total_usable
        assert sum(router.routed) == 40
