"""The serving subsystem (tpu_ddp/serve/): paged KV pool accounting,
continuous-batching scheduler invariants (docs/DESIGN.md §19), and the
engine's exactness guarantee — a request served through the paged pool
under continuous batching yields EXACTLY the tokens ``generate()``
yields, which in turn is pinned against ``model.apply`` in
tests/test_generate.py. The train→serve round trip (LM trainer
checkpoint → ``ServeEngine.from_checkpoint`` → logprob parity with
``apply``) closes the loop end to end.

Every engine in the fast tier shares ONE cache geometry
(block_size=8, blocks_per_seq=8 at max_seq_len=64), so they all share
the two memoized jitted step programs (engine.py) — the whole file
compiles the decode/prefill steps once.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models.generate import generate
from tpu_ddp.models.transformer import make_transformer, rope
from tpu_ddp.ops.pallas import paged_attention
from tpu_ddp.serve import (
    PagedKVPool,
    Request,
    Scheduler,
    ServeEngine,
    make_shared_prefix_workload,
    make_workload,
    run_load,
)
from tpu_ddp.serve.loadgen import poisson_arrivals
from tpu_ddp.utils.metrics import MetricsLogger

# One geometry for every fast-tier engine: the jitted steps are
# memoized on (model, block_size, blocks_per_seq), so this is one
# decode + one prefill compile for the whole module.
GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    return make_transformer("TransformerLM-tiny", max_seq_len=64,
                            compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def _engine(model, params, **kw):
    cfg = dict(GEOM)
    cfg.update(kw)
    return ServeEngine(model, params, **cfg)


def _prompt(L, seed=0):
    return np.random.default_rng(seed).integers(0, 1024, size=L,
                                                dtype=np.int64)


def _ref_greedy(model, params, prompt, n):
    """generate()'s continuation — the engine must match it exactly."""
    out = generate(model, params,
                   np.asarray(prompt, np.int32)[None], n)
    return np.asarray(out)[0]


def _ref_logprobs(model, params, prompt, tokens):
    """log P(token_i | prefix) straight from model.apply — the
    distribution the trainer optimized."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens, np.int32)])
    logits = np.asarray(model.apply(params, jnp.asarray(seq[None])))[0]
    lps = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
    p = len(prompt)
    return np.array([float(lps[p - 1 + i, t])
                     for i, t in enumerate(tokens)])


class TestPagedPool:
    def test_alloc_free_roundtrip(self, model):
        pool = PagedKVPool(model, num_blocks=9, block_size=8)
        assert pool.total_usable == 8 and pool.free_count == 8
        got = [pool.alloc() for _ in range(8)]
        assert len(set(got)) == 8
        assert PagedKVPool.NULL_BLOCK not in got
        assert pool.free_count == 0
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc()
        pool.free(got)
        assert pool.free_count == 8

    def test_free_misuse_is_loud(self, model):
        pool = PagedKVPool(model, num_blocks=5, block_size=8)
        b = pool.alloc()
        pool.free([b])
        with pytest.raises(ValueError, match="double free"):
            pool.free([b])
        with pytest.raises(ValueError, match="null block"):
            pool.free([PagedKVPool.NULL_BLOCK])
        with pytest.raises(ValueError, match="out of range"):
            pool.free([99])

    def test_geometry_validation(self, model):
        with pytest.raises(ValueError, match="null block"):
            PagedKVPool(model, num_blocks=1, block_size=8)
        with pytest.raises(ValueError, match="block_size"):
            PagedKVPool(model, num_blocks=4, block_size=0)
        assert PagedKVPool(model, 4, 8).blocks_for(17) == 3
        assert PagedKVPool(model, 4, 8).blocks_for(16) == 2

    def test_cache_dtype_rides_memory_policy(self, model):
        # Same vocabulary as the training-side activation policy
        # (memory/policy.py): "compute" preserves exactness, "bf16"
        # halves cache bytes under this f32 model.
        assert PagedKVPool(model, 4, 8, "compute").k.dtype \
            == jnp.float32
        assert PagedKVPool(model, 4, 8, "bf16").k.dtype == jnp.bfloat16
        with pytest.raises(ValueError):
            PagedKVPool(model, 4, 8, "fp4")


class TestScheduler:
    def _req(self, rid, p_len, max_new):
        return Request(rid=rid, prompt=np.zeros(p_len, np.int32),
                       max_new_tokens=max_new)

    def test_infeasible_request_rejected_at_enqueue(self, model):
        sched = Scheduler(PagedKVPool(model, 3, 8), num_slots=2)
        with pytest.raises(ValueError, match="KV blocks"):
            sched.enqueue(self._req(0, 20, 20))  # 5 blocks > 2 usable

    def test_fifo_head_blocking_and_reservation(self, model):
        # Pool of 4 usable blocks; A reserves all 4 worst-case, so B
        # (needing only 1) must NOT jump the... actually must not be
        # admitted at all while A's reservation holds the pool.
        sched = Scheduler(PagedKVPool(model, 5, 8), num_slots=2)
        a, b = self._req(0, 8, 24), self._req(1, 4, 4)
        sched.enqueue(a)
        sched.enqueue(b)
        admitted = sched.admit()
        assert len(admitted) == 1
        assert sched.slots[admitted[0]].request is a
        assert list(sched.queue) == [b]  # head-blocked, not skipped
        assert sched.accounting_ok()
        # Retiring A releases blocks AND reservation; B admits next.
        sched.retire(admitted[0])
        admitted = sched.admit()
        assert len(admitted) == 1
        assert sched.slots[admitted[0]].request is b
        assert sched.accounting_ok()

    def test_static_mode_drains_before_refilling(self, model):
        sched = Scheduler(PagedKVPool(model, 33, 8), num_slots=2,
                          mode="static")
        for i in range(3):
            sched.enqueue(self._req(i, 4, 4))
        first = sched.admit()
        assert len(first) == 2          # fill every slot...
        assert sched.admit() == []      # ...then nothing while live
        for i in first:
            sched.retire(i)
        assert len(sched.admit()) == 1  # refill only after full drain

    def test_mode_validation(self, model):
        with pytest.raises(ValueError, match="mode"):
            Scheduler(PagedKVPool(model, 3, 8), 2, mode="dynamic")


class TestEngineParity:
    def test_greedy_matches_generate_across_mixed_batch(self, model,
                                                        params):
        """The tentpole guarantee: continuous batching + chunked
        prefill + the paged pool change WHEN work runs, never WHAT is
        computed. Prompt lengths straddle the prefill chunk (8) and
        block size (8) boundaries; generation budgets differ so slots
        retire and refill mid-flight."""
        eng = _engine(model, params)
        cases = [(3, 6), (8, 6), (11, 6), (20, 4), (9, 12), (5, 6)]
        reqs = [eng.submit(_prompt(L, seed=i), n)
                for i, (L, n) in enumerate(cases)]
        eng.run()
        for i, ((L, n), req) in enumerate(zip(cases, reqs)):
            assert req.done and not req.cancelled
            np.testing.assert_array_equal(
                np.asarray(req.tokens),
                _ref_greedy(model, params, _prompt(L, seed=i), n),
                err_msg=f"request {i} (prompt {L}, max_new {n})")
        # Drained engine: every page back in the pool.
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.sched.accounting_ok()

    def test_logprobs_match_apply(self, model, params):
        eng = _engine(model, params)
        prompt = _prompt(10, seed=3)
        req = eng.submit(prompt, 6)
        eng.run()
        want = _ref_logprobs(model, params, prompt, req.tokens)
        np.testing.assert_allclose(np.asarray(req.logprobs), want,
                                   rtol=1e-4, atol=1e-4)

    def test_static_mode_same_tokens(self, model, params):
        # The baseline scheduler changes admission timing only.
        eng = _engine(model, params, mode="static")
        cases = [(4, 5), (9, 3), (6, 8)]
        reqs = [eng.submit(_prompt(L, seed=10 + i), n)
                for i, (L, n) in enumerate(cases)]
        eng.run()
        for i, ((L, n), req) in enumerate(zip(cases, reqs)):
            np.testing.assert_array_equal(
                np.asarray(req.tokens),
                _ref_greedy(model, params, _prompt(L, seed=10 + i), n))

    def test_bf16_cache_runs(self, model, params):
        # Semantic knob: not exactness-preserving, but must produce a
        # full-length generation through the same programs.
        eng = _engine(model, params, cache_dtype="bf16")
        assert eng.pool.k.dtype == jnp.bfloat16
        req = eng.submit(_prompt(6, seed=4), 5)
        eng.run()
        assert req.done and len(req.tokens) == 5


class TestPagedDecodeKernel:
    """The decode step on a model inside the paged kernel's predicate
    (head_dim 128, block 16; ops/pallas/paged_attention.py): attention
    reads the pool in place, interpreted on the CPU. Same guarantees as
    the gather body the tiny head_dim fixtures above keep testing."""

    GEOM = dict(num_slots=4, block_size=16, prefill_chunk=8)
    # Prompts straddle the block (16) and chunk (8) boundaries; budgets
    # differ, six requests on four slots: slots retire and refill.
    CASES = [(3, 6), (16, 6), (17, 12), (31, 4), (9, 20), (15, 3)]

    @pytest.fixture(scope="class")
    def wide(self):
        m = make_transformer("TransformerLM-tiny", num_heads=4,
                             num_kv_heads=2, d_model=512, max_seq_len=64,
                             compute_dtype=jnp.float32)
        assert m.head_dim == 128
        return m

    @pytest.fixture(scope="class")
    def wparams(self, wide):
        return wide.init(jax.random.key(1))

    def test_decode_step_holds_the_kernel_and_no_gather(self, wide,
                                                        wparams):
        eng = ServeEngine(wide, wparams, **self.GEOM)
        text = eng.lower_decode_step().as_text(debug_info=True)
        assert text.count("paged_decode_attn") >= wide.num_layers
        assert "attn/kv_write/" in text and "kv_gather" not in text
        assert "kv_gather" in eng.lower_prefill_step().as_text(
            debug_info=True)

    def test_greedy_tokens_match_generate_logprobs_match_apply(
            self, wide, wparams):
        eng = ServeEngine(wide, wparams, **self.GEOM)
        reqs = [eng.submit(_prompt(L, seed=i), n)
                for i, (L, n) in enumerate(self.CASES)]
        eng.run()
        for i, ((L, n), req) in enumerate(zip(self.CASES, reqs)):
            assert req.done and not req.cancelled
            prompt = _prompt(L, seed=i)
            np.testing.assert_array_equal(
                np.asarray(req.tokens),
                _ref_greedy(wide, wparams, prompt, n),
                err_msg=f"request {i} (prompt {L}, max_new {n})")
            np.testing.assert_allclose(
                np.asarray(req.logprobs),
                _ref_logprobs(wide, wparams, prompt, req.tokens),
                rtol=1e-4, atol=1e-4)
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.sched.accounting_ok()

    def test_one_decode_compile_over_40_steps_of_growing_lengths(
            self, wide, wparams):
        from tpu_ddp.analysis.retrace import count_compiles
        from tpu_ddp.serve.engine import _build_decode_step
        _build_decode_step.cache_clear()     # compile inside the block
        eng = ServeEngine(wide, wparams, **self.GEOM)
        reqs = [eng.submit(_prompt(L, seed=i), 40)
                for i, L in enumerate((3, 15, 16, 17))]
        with count_compiles(watch=("serve_decode",)) as seen:
            steps = 0
            while eng.step():
                steps += 1
        assert steps >= 40 and all(len(r.tokens) == 40 for r in reqs)
        assert seen.counts == {"serve_decode": 1}

    def test_poisoned_page_quarantines_exactly_its_own_request(
            self, wide, wparams, monkeypatch):
        clean = ServeEngine(wide, wparams, **self.GEOM)
        want = [clean.submit(_prompt(L, seed=i), n)
                for i, (L, n) in enumerate(self.CASES)]
        clean.run()
        monkeypatch.setenv("TPU_DDP_CHAOS_FAULTS", "nonfinite-logits@6")
        eng = ServeEngine(wide, wparams, **self.GEOM)
        got = [eng.submit(_prompt(L, seed=i), n)
               for i, (L, n) in enumerate(self.CASES)]
        with pytest.warns(UserWarning, match="quarantin"):
            eng.run()
        assert all(h.done for h in got)
        assert sum(h.quarantined for h in got) == 1
        assert eng.metrics.counters.get("serve_quarantined") == 1
        for h, w in zip(got, want):
            if not h.quarantined:
                assert list(h.tokens) == list(w.tokens)
                assert h.logprobs == w.logprobs
        assert eng.sched.accounting_ok()


class TestOneGatherOnThePool:
    """Every program that gathers K/V pages reads them from the whole
    pool in one gather (``pool[li, tables]``). Slicing a layer out
    first, ``pool[li]`` then ``[tables]``, gives the same values, but
    XLA:TPU materialises the slice: all N pages of the layer copied,
    for K and for V in every layer, to read the few a table names."""

    # Block counts unlike every other dimension of these programs
    # (slots 4, blocks a slot 8, block 8, chunk 8, widths 32-1024), so
    # a value shaped (N, ...) or (1, N, ...) can only be a pool layer.
    HOT, COLD = 37, 41
    LAYER = re.compile(rf"tensor<(?:1x)?(?:{HOT}|{COLD})x[^>]*>")
    TIERED = dict(kv_tiers=2, hbm_blocks=HOT, cold_blocks=COLD)
    # program -> (engine arguments, the method that lowers it)
    PROGRAMS = {
        "serve_prefill": ({}, "lower_prefill_step"),
        "serve_decode": ({}, "lower_decode_step"),
        "serve_spec": (dict(spec_k=2, spec_draft="self-1"),
                       "lower_spec_step"),
        "serve_decode_tiered": (TIERED, "lower_tiered_decode_step"),
        "serve_prefill_tiered": (TIERED, "lower_tiered_prefill_step"),
        "serve_prefill_cp": (dict(cp_prefill="ring"),
                             "lower_prefill_step"),
        "serve_adopt_decode": ({}, "lower_adopt_decode"),
    }

    def _lowered(self, program, model, params):
        kw, lower = self.PROGRAMS[program]
        kw = dict(GEOM, num_blocks=self.HOT, **kw)
        if program == "serve_prefill_cp":
            from tpu_ddp.parallel.mesh import (make_mesh,
                                               replicated_sharding)
            kw["mesh"] = make_mesh(jax.devices()[:2], dp=1, sp=2)
            params = jax.device_put(params,
                                    replicated_sharding(kw["mesh"]))
        if program == "serve_adopt_decode":
            from tpu_ddp.fleet.disagg import DisaggEngine as cls
        else:
            cls = ServeEngine
        return getattr(cls(model, params, **kw), lower)().as_text()

    @pytest.mark.parametrize("program", list(PROGRAMS))
    def test_no_value_of_a_layers_shape_and_pools_donated(
            self, program, model, params):
        # The fixture's model is outside the paged kernel's predicate:
        # the decode bodies here are the ones that gather.
        assert not paged_attention.supports(
            model.head_dim, GEOM["block_size"], jnp.float32,
            model.compute_dtype)
        text = self._lowered(program, model, params)
        assert "stablehlo.gather" in text
        layer = self.LAYER.findall(text)
        assert not layer, (program, sorted(set(layer)))
        # pool_k and pool_v (the hot pair where tiered) are arguments
        # of the pool's whole shape, still donated.
        main = next(ln for ln in text.splitlines()
                    if "func.func public @main" in ln)
        donated = re.findall(
            rf"tensor<{model.num_layers}x{self.HOT}x[^>]*> "
            r"\{[^%]*(?:tf\.aliasing_output|jax\.buffer_donor)",
            main.split(") -> ")[0])
        assert len(donated) == 2, (program, main[:400])

    def test_the_check_catches_a_sliced_layer(self, model, params,
                                              monkeypatch):
        """The parent's form, put back: the same check must see it."""
        from tpu_ddp.serve import engine as engine_mod

        def sliced(pool, li, tables, m):
            return pool[li][tables].reshape(
                tables.shape[0], -1, m.kv_heads, m.head_dim)

        monkeypatch.setattr(engine_mod, "gather_view", sliced)
        engine_mod._build_prefill_step.cache_clear()
        try:
            text = self._lowered("serve_prefill", model, params)
        finally:
            engine_mod._build_prefill_step.cache_clear()
        assert self.LAYER.findall(text)

    @pytest.mark.parametrize("tables_shape", [(1, 8), (4, 8)])
    def test_gather_view_is_the_sliced_gather_bit_for_bit(
            self, model, tables_shape):
        from tpu_ddp.serve.kv_pool import gather_view
        rng = np.random.default_rng(5)
        L, N, bs = 3, self.HOT, 8
        width = model.kv_heads * model.head_dim
        # bf16 bit patterns drawn whole, NaNs and infinities among
        # them: compared as bits, so a value changed in any way shows.
        bits = rng.integers(0, 2 ** 16, size=(L, N, bs, width),
                            dtype=np.uint16)
        pool = jax.lax.bitcast_convert_type(jnp.asarray(bits),
                                            jnp.bfloat16)
        tables = rng.integers(0, N, size=tables_shape).astype(np.int32)
        # the null block, a repeated id and the last block id
        tables[0, :4] = [PagedKVPool.NULL_BLOCK, N - 1, 5, 5]
        for li in range(L):
            got = jax.jit(gather_view, static_argnums=(1, 3))(
                pool, li, jnp.asarray(tables), model)
            want = bits[li][tables].reshape(
                tables_shape[0], -1, model.kv_heads, model.head_dim)
            assert got.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(jax.lax.bitcast_convert_type(
                    got, jnp.uint16)), want, err_msg=f"layer {li}")


class TestAttendChunk:
    """A prefill chunk attends in equal parts where the float32 scores
    of all its queries would pass engine.PREFILL_SCORES_BYTES (what
    keeps them in on-chip memory on the v5e; DESIGN.md §19)."""

    def test_parts_follow_the_scores_size(self, monkeypatch):
        from tpu_ddp.serve import engine as engine_mod
        calls = []
        monkeypatch.setattr(
            engine_mod, "attend_cached",
            lambda model, q, ck, cv, p: calls.append(q.shape[1]) or q)
        # The benchmark's chunk: 24 heads x 256 queries x 4096 keys in
        # float32 is 100 MB, two parts; a fast-tier chunk is one.
        for heads, c, t, want in [(24, 256, 4096, [128, 128]),
                                  (24, 128, 4096, [128]),
                                  (4, 8, 64, [8]),
                                  (64, 256, 8192, [32] * 8)]:
            calls.clear()
            q = jnp.zeros((1, c, heads, 2))
            out = engine_mod.attend_chunk(
                None, q, jnp.zeros((1, t, 1, 2)), None, jnp.arange(c))
            assert calls == want and out.shape == q.shape

    def test_parts_give_what_the_whole_gives(self, model, params,
                                             monkeypatch):
        from tpu_ddp.models.decode import attend_cached
        from tpu_ddp.serve import engine as engine_mod
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.standard_normal(
            (1, 16, model.num_heads, model.head_dim)), jnp.float32)
        ck, cv = (jnp.asarray(rng.standard_normal(
            (1, 64, model.kv_heads, model.head_dim)), jnp.float32)
            for _ in range(2))
        p = 20 + jnp.arange(16)
        whole = attend_cached(model, q, ck, cv, p)
        monkeypatch.setattr(engine_mod, "PREFILL_SCORES_BYTES",
                            4 * model.num_heads * 4 * 64)   # 4 rows
        np.testing.assert_array_equal(
            np.asarray(engine_mod.attend_chunk(model, q, ck, cv, p)),
            np.asarray(whole))

    def test_engine_in_parts_matches_generate(self, model, params,
                                              monkeypatch):
        from tpu_ddp.serve import engine as engine_mod
        monkeypatch.setattr(engine_mod, "PREFILL_SCORES_BYTES",
                            4 * model.num_heads * 2 * 64)   # 2 rows
        engine_mod._build_prefill_step.cache_clear()
        try:
            eng = _engine(model, params)
            cases = [(19, 6), (8, 5), (3, 4)]
            reqs = [eng.submit(_prompt(L, seed=70 + i), n)
                    for i, (L, n) in enumerate(cases)]
            eng.run()
        finally:
            engine_mod._build_prefill_step.cache_clear()
        for i, ((L, n), req) in enumerate(zip(cases, reqs)):
            np.testing.assert_array_equal(
                np.asarray(req.tokens),
                _ref_greedy(model, params, _prompt(L, seed=70 + i), n))


class TestLifecycle:
    def test_no_block_leak_across_120_requests(self, model, params):
        """The acceptance drill: a pool far smaller than the offered
        work, >= 100 requests admitted and retired through it, and the
        free count returns to exactly total_usable — no leaked, no
        double-freed page, with the §19 identity holding at every
        engine step."""
        eng = _engine(model, params, num_blocks=9)  # 8 usable pages
        rng = np.random.default_rng(0)
        reqs = [eng.submit(rng.integers(0, 1024, size=int(p)), int(n))
                for p, n in zip(rng.integers(3, 9, size=120),
                                rng.integers(2, 7, size=120))]
        steps = 0
        while eng.step():
            steps += 1
            assert eng.sched.accounting_ok(), f"leak at step {steps}"
        assert all(r.done and not r.cancelled for r in reqs)
        assert eng.pool.free_count == eng.pool.total_usable == 8
        assert eng.metrics.counters["serve_admitted"] == 120
        assert eng.metrics.counters["serve_retired"] == 120

    def test_completion_order_is_fifo_under_pressure(self, model,
                                                     params):
        # 2 usable pages, each request worst-cases to 2: strictly one
        # live request at a time, so completion order == submit order
        # (the no-starvation invariant, observed from the outside).
        eng = _engine(model, params, num_blocks=3)
        reqs = [eng.submit(_prompt(6, seed=20 + i), 6)
                for i in range(3)]
        eng.run()
        assert all(r.done for r in reqs)
        finished = [r.finished_at for r in reqs]
        assert finished == sorted(finished)

    def test_cancel_queued_and_live(self, model, params):
        eng = _engine(model, params, num_blocks=3)  # one live at a time
        a = eng.submit(_prompt(6, seed=30), 6)
        b = eng.submit(_prompt(6, seed=31), 6)
        assert eng.cancel(b)           # still queued: just drop it
        eng.step()                     # a is admitted + prefilling
        assert eng.cancel(a)           # live: slot + pages come back
        assert a.cancelled and b.cancelled
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.sched.accounting_ok()
        assert not eng.cancel(a)       # nothing left to cancel
        assert eng.metrics.counters["serve_cancelled"] == 2
        eng.run()
        assert a.tokens == [] or len(a.tokens) < 6  # never completed

    def test_cancel_mid_prefill_frees_reserved_blocks(self, model,
                                                      params):
        """Regression: a request cancelled BETWEEN prefill chunks (its
        prompt spans several) must hand back every reserved page, not
        just the ones already written — a leak here strangles the pool
        one cancelled long prompt at a time."""
        eng = _engine(model, params)
        a = eng.submit(_prompt(20, seed=32), 6)  # 3 chunks of 8
        eng.step()                     # admitted + first chunk only
        s = [x for x in eng.sched.slots if x is not None][0]
        assert s.phase == "prefill" and s.prefill_done < 20
        assert eng.cancel(a)
        assert a.cancelled and a.done
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.sched.accounting_ok()
        assert not eng.step()          # engine fully idle again

    def test_cancel_drops_pending_disagg_edge_transfer(self, model,
                                                       params):
        """Regression (fleet half of the same bug): a request whose
        prefill finished but whose KV transfer still sits on the
        prefill->decode edge must be cancellable — the transfer is
        dropped and never adopted into the decode pool."""
        from tpu_ddp.fleet import DisaggEngine
        # Decode pool of 2 usable pages: exactly one live request.
        eng = DisaggEngine(model, params, num_blocks=3, **GEOM)
        a = eng.submit(_prompt(9, seed=33), 6)   # 2 blocks worst-case
        b = eng.submit(_prompt(9, seed=34), 6)
        # Step until b's transfer is parked on the edge (a holds the
        # whole decode pool, so the adopter's reservation check gates).
        for _ in range(8):
            eng.step()
            if eng.edge.queue:
                break
        assert [t.request for t in eng.edge.queue] == [b]
        assert eng.cancel(b)
        assert b.cancelled and b.done
        assert len(eng.edge.queue) == 0
        assert eng.edge.stats()["dropped"] == 1
        assert eng.accounting_ok()
        eng.run()                       # a finishes untouched
        assert a.done and not a.cancelled and len(a.tokens) == 6
        # Every page of both pools comes home; b was never adopted.
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.prefill_pool.free_count \
            == eng.prefill_pool.total_usable
        assert eng.metrics.counters["fleet_adopted"] == 1

    def test_eos_stops_early_and_frees_slot(self, model, params):
        prompt = _prompt(5, seed=40)
        full = _ref_greedy(model, params, prompt, 6)
        eos = int(full[2])
        eng = _engine(model, params)
        req = eng.submit(prompt, 6, eos_id=eos)
        eng.run()
        assert req.done
        np.testing.assert_array_equal(np.asarray(req.tokens), full[:3])
        assert eng.pool.free_count == eng.pool.total_usable

    def test_streaming_callback_order(self, model, params):
        seen = []
        eng = _engine(model, params)
        req = eng.submit(_prompt(7, seed=41), 5, on_token=seen.append)
        eng.run()
        assert seen == req.tokens and len(seen) == 5
        assert req.ttft_s is not None and req.ttft_s >= 0

    def test_submit_validation(self, model, params):
        eng = _engine(model, params)
        with pytest.raises(ValueError, match=">= 1 token"):
            eng.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(_prompt(4), 0)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(_prompt(60), 10)
        with pytest.raises(ValueError, match="temperature"):
            eng.submit(_prompt(4), 2, temperature=-0.5)

    def test_infeasible_submit_names_the_pool(self, model, params):
        eng = _engine(model, params, num_blocks=3)
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(_prompt(10), 20)  # 4 worst-case > 2 usable


class TestStepAhead:
    """The plain engine dispatches a decode step before it reads the
    last one back (docs/DESIGN.md section 19). The reference is the same
    engine brought to rest after every step (``run(max_steps=1)``): it
    feeds every pending token from the host, as the engine did before
    it ran ahead, so streams must agree bit for bit."""

    # (prompt, max_new, temperature, seed, eos index or None): budgets
    # of one and many, an EOS mid-stream, greedy and sampled, a prompt
    # of two chunks; the last two are submitted with a step in flight.
    MIX = [(5, 1, 0.0, 0, None), (7, 9, 0.0, 1, None),
           (6, 8, 0.0, 2, 3), (13, 6, 0.9, 3, None),
           (4, 7, 0.7, 4, None), (9, 5, 0.0, 5, None),
           (3, 6, 1.1, 6, 2)]
    LATE = 2        # how many of MIX arrive while the engine is running

    @staticmethod
    def _submit(eng, case, eos=None):
        L, n, temp, seed, _ = case
        return eng.submit(_prompt(L, seed=80 + seed), n, temperature=temp,
                          seed=seed, eos_id=eos)

    @classmethod
    def _serve(cls, eng, eos, step):
        """Serve MIX: all but the last LATE at once, those after three
        steps. ``step`` advances the engine once."""
        early = len(cls.MIX) - cls.LATE
        reqs = [cls._submit(eng, c, e)
                for c, e in zip(cls.MIX[:early], eos)]
        for _ in range(3):
            step(eng)
        in_flight = eng._unread is not None
        reqs += [cls._submit(eng, c, e)
                 for c, e in zip(cls.MIX[early:], eos[early:])]
        while step(eng):
            pass
        return reqs, in_flight

    @pytest.fixture(scope="class")
    def served(self, model, params):
        def eos_of(case):
            # the token the request would sample at its EOS index
            if case[4] is None:
                return None
            probe = _engine(model, params)
            r = self._submit(probe, case)
            probe.run()
            return int(r.tokens[case[4]])

        eos = [eos_of(c) for c in self.MIX]
        ahead = _engine(model, params)
        got, in_flight = self._serve(ahead, eos, lambda e: e.step())
        assert in_flight, "the late requests met an engine at rest"
        rested = _engine(model, params)
        want, _ = self._serve(rested, eos,
                              lambda e: e.run(max_steps=1) > 0)
        return {"eos": eos, "ahead": ahead, "got": got, "want": want,
                "rested": rested}

    @pytest.mark.parametrize("i", range(len(MIX)))
    def test_stream_is_bitwise_the_rested_engines(self, served, model,
                                                  params, i):
        L, n, temp, seed, at = self.MIX[i]
        got, want = served["got"][i], served["want"][i]
        assert got.done and want.done and not got.cancelled
        assert got.tokens == want.tokens
        assert got.logprobs == want.logprobs          # floats, exactly
        assert got.token_versions == want.token_versions == [0] * len(
            got.tokens)
        assert len(got.token_times) == len(got.tokens)
        assert len(got.tokens) == (n if at is None else at + 1)
        if temp == 0.0:
            np.testing.assert_array_equal(
                np.asarray(got.tokens),
                _ref_greedy(model, params, _prompt(L, seed=80 + seed),
                            n)[:len(got.tokens)])

    def test_both_engines_end_empty_and_the_ahead_one_ran_ahead(
            self, served):
        for key in ("ahead", "rested"):
            eng = served[key]
            assert eng._unread is None
            assert eng.pool.free_count == eng.pool.total_usable
            assert eng.accounting_ok() and eng.tenant_accounting_ok()
        c = served["ahead"].metrics.counters
        # (MIX[0] ends on its first token, so the first decode step of
        # all finds that token unread and no decode row: at rest)
        assert c["serve_decode_at_rest"] == 1
        assert c["serve_decode_ahead"] >= 8
        r = served["rested"].metrics.counters
        assert "serve_decode_ahead" not in r

    def test_step_false_and_run_mean_nothing_in_flight(self, model,
                                                       params):
        eng = _engine(model, params)
        reqs = [eng.submit(_prompt(5 + i, seed=90 + i), 4 + i)
                for i in range(3)]
        assert eng.step() and eng._unread is not None
        assert eng.run(max_steps=2) == 2 and eng._unread is None
        assert not all(r.done for r in reqs)
        flights = []
        while eng.step():
            flights.append(eng._unread is not None)
        assert eng._unread is None and all(r.done for r in reqs)
        # the last working step only reads the one before it back
        assert flights[-1] is False and any(flights)
        assert not eng.step() and eng.run() == 0

    def test_a_step_dispatches_before_it_reads(self, model, params):
        """In steady state every step finds its predecessor unread:
        ``serve_decode_ahead`` rises by one a step and
        ``serve_decode_at_rest`` stays where the first step left it;
        and the tokens a step hands out are the step before's."""
        eng = _engine(model, params)
        reqs = [eng.submit(_prompt(4, seed=95 + i), 12) for i in range(4)]
        eng.step()                  # four slots: a chunk a step
        c = eng.metrics.counters
        assert c["serve_decode_at_rest"] == 1
        assert "serve_decode_ahead" not in c
        assert reqs[0].tokens == []             # sampled, not read yet
        for n in range(1, 9):
            before = [len(r.tokens) for r in reqs]
            eng.step()
            assert c["serve_decode_ahead"] == n
            assert c["serve_decode_at_rest"] == 1
            if n >= 5:
                # all four decode; each got exactly one token, and a
                # second sits on the device (``ahead``)
                assert [len(r.tokens) for r in reqs] \
                    == [b + 1 for b in before]
                assert all(s.ahead == 1 for s in eng.sched.slots)
        eng.run()
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_greedy(model, params, _prompt(4, seed=95 + i), 12))

    def test_ahead_means_a_decode_step_unread_at_k0(self, model, params):
        """``ahead`` is 1 where the DECODE step before is unread: a
        final chunk's first token alone on the device is the engine at
        rest. The speculative step reads back at once and counts
        neither way."""
        eng = _engine(model, params)
        eng.submit(_prompt(5, seed=97), 1)      # ends on its first token
        late = eng.submit(_prompt(6, seed=98), 4)
        eng.step()
        assert eng._unread.firsts and not eng._unread.rows
        eng.step()                  # late's chunk and its first row
        c = eng.metrics.counters
        assert c["serve_decode_at_rest"] == 1 and late.tokens == []
        assert "serve_decode_ahead" not in c
        eng.step()
        assert c["serve_decode_ahead"] == 1
        eng.run()
        spec = _engine(model, params, spec_k=2, spec_draft="self-1")
        r = spec.submit(_prompt(6, seed=98), 4)
        spec.run()
        assert r.tokens == late.tokens
        assert not {"serve_decode_ahead", "serve_decode_at_rest"} \
            & set(spec.metrics.counters)

    @pytest.mark.parametrize("busy", [False, True],
                             ids=["engine_at_rest", "decode_in_flight"])
    def test_first_token_is_out_by_the_step_after_its_chunk(
            self, model, params, busy):
        """What running ahead costs a new request (PERF.md section 7,
        time to first token): its first token waits on the device for
        ONE ``step()`` past the step that dispatched its final chunk,
        and no longer, with or without another request's decode step
        in flight before the chunk."""
        eng = _engine(model, params)
        if busy:
            eng.submit(_prompt(4, seed=140), 30)
            for _ in range(3):
                eng.step()
            assert eng._unread is not None and eng._unread.rows
        req = eng.submit(_prompt(13, seed=141), 5)      # two chunks
        eng.step()
        slot = next(s for s in eng.sched.slots
                    if s is not None and s.request is req)
        assert slot.phase == "prefill" and slot.prefill_done == 8
        eng.step()                                      # the final chunk
        assert slot.phase == "decode" and slot.first_unread
        assert req.tokens == [] and req.first_token_at is None
        eng.step()
        # ... and the row the same step decoded from it, on the device
        assert len(req.tokens) == 2 and not slot.first_unread
        assert req.first_token_at == req.token_times[0]
        eng.run()
        np.testing.assert_array_equal(
            np.asarray(req.tokens),
            _ref_greedy(model, params, _prompt(13, seed=141), 5))

    # ---- what brings the engine to rest ------------------------------

    # (prompt, max_new, temperature) of the four requests of _midflight
    CASES = [(6, 14, 0.0), (9, 12, 0.8), (5, 16, 0.0), (12, 10, 0.6)]

    def _midflight(self, model, params, monkeypatch=None, chaos=None,
                   **kw):
        """Four requests, stepped until all decode with a step in
        flight; and the streams an undisturbed engine gives them."""
        def submit(e):
            return [e.submit(_prompt(L, seed=110 + i), n, temperature=t,
                             seed=i, tenant="ab"[i % 2])
                    for i, (L, n, t) in enumerate(self.CASES)]

        clean = _engine(model, params, **kw)
        want = submit(clean)
        clean.run()
        if chaos:
            monkeypatch.setenv("TPU_DDP_CHAOS_FAULTS", chaos)
        eng = _engine(model, params, **kw)
        got = submit(eng)
        for _ in range(7):
            eng.step()
        assert eng._unread is not None and len(eng._unread.rows) == 4
        assert all(0 < len(r.tokens) < r.max_new_tokens for r in got)
        return eng, got, want

    @staticmethod
    def _books_ok(eng):
        return eng.accounting_ok() and eng.tenant_accounting_ok()

    def test_cancel_hands_out_the_step_in_flight_first(self, model,
                                                       params):
        eng, got, want = self._midflight(model, params)
        had = [len(r.tokens) for r in got]
        assert eng.cancel(got[1])
        assert eng._unread is None and self._books_ok(eng)
        # every request, the victim too, first got its token in flight
        assert [len(r.tokens) for r in got] == [h + 1 for h in had]
        eng.step()
        assert eng.metrics.counters["serve_decode_at_rest"] == 2
        eng.run()
        for i, (g, w) in enumerate(zip(got, want)):
            if i == 1:
                assert g.cancelled and g.tokens == w.tokens[:had[1] + 1]
            else:
                assert g.tokens == w.tokens and g.logprobs == w.logprobs
        assert eng.pool.free_count == eng.pool.total_usable
        assert self._books_ok(eng)

    def test_cancel_after_the_last_token_in_flight_finds_it_done(
            self, model, params):
        eng = _engine(model, params)
        req = eng.submit(_prompt(5, seed=120), 3)
        while len(req.tokens) < 2:
            eng.step()
        assert not req.done and eng._unread is not None
        assert not eng.cancel(req)          # the token in flight ended it
        assert req.done and not req.cancelled and len(req.tokens) == 3
        assert self._books_ok(eng)
        assert eng.pool.free_count == eng.pool.total_usable

    def test_drain_loses_and_replays_no_token(self, model, params):
        from tpu_ddp.fleet.resilience import continuation_of
        eng, got, want = self._midflight(model, params)
        had = [len(r.tokens) for r in got]
        harvested = eng.drain()
        assert eng._unread is None and self._books_ok(eng)
        assert [r.rid for r in harvested] == [r.rid for r in got]
        assert [len(r.tokens) for r in got] == [h + 1 for h in had]
        assert eng.pool.free_count == eng.pool.total_usable
        assert not eng.step()
        # replayed elsewhere, each continues exactly where it stopped
        other = _engine(model, params)
        for (_, _, t), g, w in zip(self.CASES, got, want):
            prompt, left = continuation_of(g)
            rest = other.submit(prompt, left, temperature=t, seed=g.seed)
            other.run()
            assert g.tokens + rest.tokens == w.tokens

    def test_swap_params_stamps_the_version_of_the_dispatch(self, model,
                                                            params):
        eng, got, want = self._midflight(model, params)
        had = [len(r.tokens) for r in got]
        eng.swap_params(eng.params, 7)      # same weights, new version
        assert eng._unread is None
        eng.step()
        assert eng.metrics.counters["serve_decode_at_rest"] == 2
        eng.run()
        for g, w, h in zip(got, want, had):
            assert g.tokens == w.tokens and g.logprobs == w.logprobs
            # the token in flight at the flip was dispatched on 0
            assert g.token_versions == [0] * (h + 1) \
                + [7] * (len(g.tokens) - h - 1)
        assert self._books_ok(eng)

    def test_nonfinite_drill_finds_the_engine_at_rest(self, model,
                                                      params, monkeypatch):
        eng, got, want = self._midflight(
            model, params, monkeypatch, chaos="nonfinite-logits@8")
        scrubbed, victims = [], []
        scrub, quarantine = eng.pool.scrub, eng._quarantine
        monkeypatch.setattr(eng.pool, "scrub",
                            lambda b: (scrubbed.extend(b), scrub(b))[1])
        monkeypatch.setattr(
            eng, "_quarantine",
            lambda i: (victims.append(list(eng.sched.slots[i].blocks)),
                       quarantine(i))[1])
        at_rest = eng.metrics.counters["serve_decode_at_rest"]
        with pytest.warns(UserWarning, match="quarantin"):
            eng.run()
        assert eng.metrics.counters["serve_decode_at_rest"] == at_rest + 1
        assert [h.quarantined for h in got] == [True, False, False, False]
        assert len(victims) == 1 and sorted(scrubbed) == sorted(victims[0])
        assert got[0].tokens == want[0].tokens[:len(got[0].tokens)]
        assert len(got[0].tokens) < len(want[0].tokens)
        for g, w in zip(got[1:], want[1:]):
            assert g.tokens == w.tokens and g.logprobs == w.logprobs
        assert self._books_ok(eng)
        assert eng.pool.free_count == eng.pool.total_usable

    def test_nonfinite_drill_is_asked_only_of_a_step_that_decodes(
            self, model, params, monkeypatch, capsys):
        """``poison_due`` brings the engine to rest and marks nothing;
        ``poison_fires`` (which announces, and spends a one-shot drill)
        is asked only where there is a decode slot to poison."""
        monkeypatch.setenv("TPU_DDP_CHAOS_FAULTS", "nonfinite-logits@1")
        eng = _engine(model, params)
        req = eng.submit(_prompt(13, seed=150), 4)  # step 1: half a prompt
        assert eng.chaos.poison_due(1) and eng.chaos.poison_due(1)
        assert not eng.chaos.poison_due(2)
        eng.run()
        assert "[chaos]" not in capsys.readouterr().out
        assert not req.quarantined and len(req.tokens) == 4

    def test_row_past_a_quarantine_is_dropped_and_scrubbed_after(
            self, model, params):
        """Non-finite logits are an end the host cannot see: when the
        flag is read, one more row of the victim is in flight. It wrote
        into the victim's own blocks, which are scrubbed in stream
        order after it, and its sample is dropped."""
        eng, got, want = self._midflight(model, params)
        victim = eng.sched.slots[2]
        blocks = list(victim.blocks)
        # behind the step in flight in the stream: the next step reads it
        eng.pool.v = eng.pool.v.at[:, blocks[0]].set(jnp.nan)
        had = len(got[2].tokens)
        with pytest.warns(UserWarning, match="quarantin"):
            eng.step()      # dispatches on the NaN, reads the clean step
            assert len(got[2].tokens) == had + 1 and not got[2].done
            eng.step()      # reads the flag with one more row in flight
        assert got[2].quarantined and len(got[2].tokens) == had + 1
        assert eng.sched.slots[2] is None and victim.ahead == 1
        assert eng._unread.rows[2] is victim            # the row past it
        # scrubbed after that row wrote: its pages hold nothing at all
        mine = np.asarray(sorted(set(blocks) | set(victim.blocks)))
        assert not np.asarray(eng.pool.v[:, mine]).any()
        assert not np.asarray(eng.pool.k[:, mine]).any()
        eng.run()
        assert self._books_ok(eng)
        assert eng.pool.free_count == eng.pool.total_usable
        assert np.isfinite(np.asarray(eng.pool.v)).all()
        assert got[2].tokens == want[2].tokens[:had + 1]
        for i in (0, 1, 3):
            assert got[i].tokens == want[i].tokens
            assert got[i].logprobs == want[i].logprobs

    def test_row_past_an_eos_lands_in_the_slots_own_freed_blocks(
            self, model, params):
        """EOS is read one step late: the row dispatched past it wrote
        into blocks that are free again by then, and a request admitted
        into them is served exactly."""
        probe = _engine(model, params)
        p = probe.submit(_prompt(6, seed=130), 12)
        probe.run()
        eng = _engine(model, params, num_blocks=4)      # 3 usable pages
        a = eng.submit(_prompt(6, seed=130), 12, eos_id=int(p.tokens[4]))
        b = eng.submit(_prompt(7, seed=131), 10)        # waits for a's
        while not a.done:
            eng.step()
            assert eng.accounting_ok()
        stale = eng._unread
        assert a.tokens == p.tokens[:5]
        assert stale is not None and len(stale.rows) == 1   # past the EOS
        eng.run()
        assert b.done and eng.pool.free_count == eng.pool.total_usable
        np.testing.assert_array_equal(
            np.asarray(b.tokens),
            _ref_greedy(model, params, _prompt(7, seed=131), 10))
        assert a.tokens == p.tokens[:5]                 # nothing appended


class TestSampling:
    def test_seeded_sampling_survives_rebatching(self, model, params):
        """Sampling is keyed by (request seed, absolute position) —
        stateless — so the SAME request produces the SAME tokens no
        matter which neighbors share its batch. This is the property
        that makes serving results reproducible under load."""
        prompt = _prompt(6, seed=50)
        alone = _engine(model, params)
        r1 = alone.submit(prompt, 6, temperature=1.0, seed=7)
        alone.run()
        crowded = _engine(model, params)
        for i in range(3):  # different neighbors, different seeds
            crowded.submit(_prompt(5 + i, seed=60 + i), 4,
                           temperature=1.0, seed=100 + i)
        r2 = crowded.submit(prompt, 6, temperature=1.0, seed=7)
        crowded.run()
        assert r1.tokens == r2.tokens

    def test_different_seeds_differ(self, model, params):
        prompt = _prompt(6, seed=51)
        eng = _engine(model, params)
        a = eng.submit(prompt, 6, temperature=1.0, seed=1)
        b = eng.submit(prompt, 6, temperature=1.0, seed=2)
        eng.run()
        assert a.tokens != b.tokens


class TestKnobs:
    def test_env_defaults_flow_into_engine(self, model, params,
                                           monkeypatch):
        monkeypatch.setenv("TPU_DDP_SERVE_SLOTS", "4")
        monkeypatch.setenv("TPU_DDP_SERVE_BLOCK", "8")
        monkeypatch.setenv("TPU_DDP_SERVE_PREFILL_CHUNK", "8")
        monkeypatch.setenv("TPU_DDP_SERVE_CACHE_DTYPE", "f32")
        eng = ServeEngine(model, params)  # no explicit knobs
        assert eng.num_slots == 4
        assert eng.block_size == 8
        assert eng.prefill_chunk == 8
        assert eng.pool.dtype == jnp.float32

    def test_junk_env_values_rejected(self, monkeypatch):
        from tpu_ddp.utils.config import TrainConfig
        monkeypatch.setenv("TPU_DDP_SERVE_CACHE_DTYPE", "fp4")
        with pytest.raises(ValueError,
                           match="TPU_DDP_SERVE_CACHE_DTYPE"):
            TrainConfig()
        monkeypatch.delenv("TPU_DDP_SERVE_CACHE_DTYPE")
        monkeypatch.setenv("TPU_DDP_SERVE_SLOTS", "0")
        with pytest.raises(ValueError, match="TPU_DDP_SERVE_SLOTS"):
            TrainConfig()


class TestMetrics:
    def test_counters_and_gauges(self, model, params):
        m = MetricsLogger(None)
        eng = _engine(model, params, metrics=m)
        for i in range(3):
            eng.submit(_prompt(4 + i, seed=70 + i), 3)
        eng.run()
        assert m.counters["serve_submitted"] == 3
        assert m.counters["serve_admitted"] == 3
        assert m.counters["serve_retired"] == 3
        assert m.gauge_summary("serve_ttft_ms")["count"] == 3
        occ = m.gauge_summary("serve_slot_occupancy")
        assert occ is not None and 0.0 <= occ["max"] <= 1.0
        assert m.gauge_summary("serve_queue_depth") is not None


class TestLoadgen:
    def test_arrivals_and_workload_are_seeded(self):
        a = poisson_arrivals(16, rate=5.0, seed=3)
        b = poisson_arrivals(16, rate=5.0, seed=3)
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) > 0) and np.all(a > 0)
        w1 = make_workload(8, 1024, seed=1)
        w2 = make_workload(8, 1024, seed=1)
        assert w1 == w2
        assert all(4 <= len(s.prompt) <= 16 for s in w1)

    def test_run_load_measures_and_completes(self, model, params):
        specs = make_workload(6, 1024, seed=2, prompt_len=(3, 9),
                              max_new=(2, 6))
        m = run_load(_engine(model, params), specs, rate=500.0,
                     slo_ttft_ms=1e4)
        assert m["n_requests"] == 6
        assert m["total_tokens"] == sum(s.max_new_tokens for s in specs)
        assert m["ttft_p50_ms"] <= m["ttft_p99_ms"]
        # The full latency anatomy: e2e covers TTFT, and with every
        # spec generating >= 2 tokens TPOT is measurable everywhere.
        assert m["e2e_p50_ms"] <= m["e2e_p99_ms"]
        assert m["e2e_p99_ms"] >= m["ttft_p99_ms"]
        assert m["tpot_p50_ms"] is not None
        assert 0.0 <= m["tpot_p50_ms"] <= m["tpot_p99_ms"]
        assert m["tpot_mean_ms"] > 0.0
        assert m["slo_attained"] == 1.0  # absurdly lax SLO
        assert m["goodput_tokens_per_sec"] == m["tokens_per_sec"]

    def test_shared_prefix_workload_is_seeded_and_shared(self):
        w1 = make_shared_prefix_workload(6, 1024, seed=3, prefix_len=16)
        w2 = make_shared_prefix_workload(6, 1024, seed=3, prefix_len=16)
        assert w1 == w2
        heads = {s.prompt[:16] for s in w1}
        assert len(heads) == 1           # one shared system prompt
        assert len({s.prompt for s in w1}) > 1  # distinct tails

    @pytest.mark.slow  # wall-clock load drill: two timed runs at 2x
    # saturation plus a calibration run (~tens of seconds)
    def test_continuous_beats_static_goodput_under_overload(
            self, model, params):
        """The subsystem's reason to exist, as a regression test: at
        2x the measured saturation rate and a TTFT SLO derived from an
        unloaded probe, continuous batching delivers at least the
        goodput of static batching (the sweep artifact enforces
        strictly-greater; >= here keeps the test robust to timer
        noise on loaded CI hosts)."""
        from tpu_ddp.serve import calibrate_rate
        specs = make_workload(24, 1024, seed=5, prompt_len=(4, 13),
                              max_new=(4, 17))
        warm = _engine(model, params)
        for sp in specs[:2]:
            warm.submit(sp.prompt, sp.max_new_tokens)
        warm.run()
        probe = _engine(model, params)
        h = probe.submit(specs[0].prompt, specs[0].max_new_tokens)
        probe.run()
        slo = max(50.0, 10.0 * h.ttft_s * 1e3)
        cap = calibrate_rate(lambda: _engine(model, params), specs)
        cont = run_load(_engine(model, params), specs, 2.0 * cap,
                        seed=9, slo_ttft_ms=slo)
        stat = run_load(_engine(model, params, mode="static"), specs,
                        2.0 * cap, seed=9, slo_ttft_ms=slo)
        assert cont["goodput_tokens_per_sec"] \
            >= stat["goodput_tokens_per_sec"]


class TestDecodeCore:
    def test_rope_batched_positions_match_shared(self):
        # The (B, L) generalization that continuous batching needs:
        # each row at its own offset must equal the 1-D call at that
        # offset (the 1-D path is the pre-refactor program).
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 5, 4, 8)), jnp.float32)
        p0, p1 = np.arange(3, 8), np.arange(11, 16)
        batched = rope(x, jnp.asarray(np.stack([p0, p1])))
        np.testing.assert_allclose(
            np.asarray(batched[0]),
            np.asarray(rope(x[:1], jnp.asarray(p0))[0]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(batched[1]),
            np.asarray(rope(x[1:], jnp.asarray(p1))[0]), rtol=1e-6)

    def test_attend_cached_per_row_positions(self, model):
        from tpu_ddp.models.decode import attend_cached
        rng = np.random.default_rng(1)
        S = 16
        q = jnp.asarray(rng.normal(size=(2, 1, model.num_heads,
                                         model.head_dim)), jnp.float32)
        ck = jnp.asarray(rng.normal(size=(2, S, model.kv_heads,
                                          model.head_dim)), jnp.float32)
        cv = jnp.asarray(rng.normal(size=ck.shape), jnp.float32)
        pos = jnp.asarray([[3], [9]])
        got = attend_cached(model, q, ck, cv, pos)
        for b in range(2):
            want = attend_cached(model, q[b:b + 1], ck[b:b + 1],
                                 cv[b:b + 1], pos[b])
            np.testing.assert_allclose(np.asarray(got[b]),
                                       np.asarray(want[0]), rtol=1e-6)


class TestTrainServeRoundTrip:
    def _train(self, model, mesh_devices, tmp_path, **trainer_kw):
        from tpu_ddp.ops.optim import SGD
        from tpu_ddp.parallel.mesh import make_mesh
        from tpu_ddp.train.lm import LMTrainer, make_lm_batch
        dp = len(mesh_devices)
        tr = LMTrainer(model, make_mesh(mesh_devices, dp=dp),
                       optimizer=SGD(learning_rate=0.1, momentum=0.9),
                       **trainer_kw)
        state = tr.init_state(seed=11)
        tokens = np.random.default_rng(2).integers(0, 1024,
                                                   size=(4, 33))
        x, y = tr.put_batch(*make_lm_batch(tokens))
        for _ in range(2):
            state, _ = tr.train_step(state, x, y)
        tr.save_checkpoint(str(tmp_path), state)
        return state

    def test_checkpoint_to_engine_logprob_parity(self, model, devices,
                                                 tmp_path):
        """The satellite the subsystem exists for: train a model,
        checkpoint through the canonical path, serve it — and the
        engine streams per-token logprobs equal to ``model.apply`` on
        the trained params, with tokens equal to ``generate()``'s."""
        state = self._train(model, devices[:1], tmp_path)
        eng = ServeEngine.from_checkpoint(model, str(tmp_path), **GEOM)
        prompt = _prompt(9, seed=80)
        req = eng.submit(prompt, 6)
        eng.run()
        trained = jax.tree.map(jnp.asarray, state.params)
        np.testing.assert_array_equal(
            np.asarray(req.tokens),
            _ref_greedy(model, trained, prompt, 6))
        np.testing.assert_allclose(
            np.asarray(req.logprobs),
            _ref_logprobs(model, trained, prompt, req.tokens),
            rtol=1e-4, atol=1e-4)

    # The under-budget and cross-strategy cells keep the restore path
    # fast; this adds only the tp-serving placement on top.
    @pytest.mark.slow
    def test_checkpoint_over_budget_serves_tensor_parallel(
            self, model, devices, tmp_path):
        """A checkpoint too big for one chip's param budget routes
        through shard_decode_params: params split Megatron-style over
        an mp mesh, both jitted steps run under GSPMD — and the tokens
        equal the dense engine's (column-parallel projections are
        communication-free; the row-parallel all-reduces change
        summation order, which greedy argmax absorbs)."""
        state = self._train(model, devices[:1], tmp_path)
        trained = jax.tree.map(jnp.asarray, state.params)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(trained))
        eng = ServeEngine.from_checkpoint(
            model, str(tmp_path), param_budget_bytes=nbytes // 2,
            shard_devices=devices[:4], **GEOM)
        assert eng.mesh is not None
        wo = eng.params["blocks"][0]["wo"]
        assert not wo.sharding.is_fully_replicated
        prompt = _prompt(9, seed=82)
        req = eng.submit(prompt, 6)
        eng.run()
        np.testing.assert_array_equal(
            np.asarray(req.tokens),
            _ref_greedy(model, trained, prompt, 6))

    def test_checkpoint_under_budget_stays_dense(self, model, devices,
                                                 tmp_path):
        state = self._train(model, devices[:1], tmp_path)
        nbytes = sum(x.nbytes for x in
                     jax.tree.leaves(state.params))
        eng = ServeEngine.from_checkpoint(
            model, str(tmp_path), param_budget_bytes=2 * nbytes,
            **GEOM)
        assert eng.mesh is None   # round-12 single-chip path untouched

    def test_indivisible_tp_degree_refused(self, model, params,
                                           devices):
        from tpu_ddp.parallel.tensor_parallel import shard_decode_params
        with pytest.raises(ValueError, match="divisible"):
            shard_decode_params(model, params, devices[:3])

    def test_training_sharded_model_config_still_refused(self):
        # The pre-existing refusal: serving shards PARAMS of a dense
        # model config; a model CONFIGURED for training-time tp/sp/ep
        # layouts is still rejected loudly.
        from tpu_ddp.models.transformer import make_transformer
        tp_model = make_transformer("TransformerLM-tiny",
                                    max_seq_len=64, tp_axis="mp",
                                    tp_size=2)
        with pytest.raises(ValueError, match="dense"):
            ServeEngine(tp_model, {}, **GEOM)

    def test_cross_strategy_checkpoint_restores_dense(self, model,
                                                      devices,
                                                      tmp_path):
        """dense_params_from_checkpoint against a checkpoint written
        by a DIFFERENT strategy (dp=2 + ZeRO-1 sharded optimizer):
        the artifact is canonical, so the dense restore must equal the
        training-time params leaf-for-leaf and serve identically."""
        from tpu_ddp.models.decode import dense_params_from_checkpoint
        state = self._train(model, devices[:2], tmp_path,
                            opt_sharding="zero1")
        dense = dense_params_from_checkpoint(model, str(tmp_path))
        for a, b in zip(jax.tree.leaves(dense),
                        jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        eng = ServeEngine(model, dense, **GEOM)
        prompt = _prompt(5, seed=81)
        req = eng.submit(prompt, 4)
        eng.run()
        np.testing.assert_array_equal(
            np.asarray(req.tokens),
            _ref_greedy(model, dense, prompt, 4))


class TestDenseProgramsPinned:
    """``serve_decode`` and ``serve_prefill`` for the dense model at
    ``sc2-serve-gen``'s geometry (StarCoder2-3B widths, 30 layers, 32
    slots x 4096 positions, block 16, chunk 256), lowered from shapes
    alone. The lowered text (no source locations in it) is what it was
    before the engine learned to carry a second model family's recurrent
    state (PR 33 made both hashes on the parent, commit a0a6f36, and on
    the change: the same): the dense cell's programs did not move. A PR
    that means to change the dense programs updates the hashes, and says
    so; one that does not mean to has found out that it did."""

    PINNED = {
        "serve_decode": "8c2e2a407528bdef7124c5c8371c3204"
                        "ad9f80097077bd58da066fb9759b7630",
        "serve_prefill": "c719994826e0241107446259f665cbfe"
                         "2381502cef14b703a47a482dedbe6f26",
    }

    def test_lowered_text_is_the_parents(self):
        import hashlib
        import json
        from pathlib import Path

        from tpu_ddp.models.transformer import TransformerLM
        from tpu_ddp.serve import engine as engine_mod

        root = Path(__file__).resolve().parents[1]
        cfg = json.loads((root / "benchmark/configs/starcoder2-3b.json")
                         .read_text())
        geo = cfg["serve"]
        S, B, C = geo["num_slots"], geo["block_size"], geo["prefill_chunk"]
        bps = geo["max_seq_len"] // B
        m = TransformerLM(
            name=cfg["name"], vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
            max_seq_len=geo["max_seq_len"], param_dtype=jnp.bfloat16)
        sds = jax.ShapeDtypeStruct
        i32, f32 = jnp.int32, jnp.float32
        p = jax.eval_shape(m.init, jax.random.key(0))
        pool = sds((m.num_layers, S * bps + 1, B, m.kv_heads * m.head_dim),
                   jnp.bfloat16)
        lowered = {
            "serve_decode": engine_mod._build_decode_step(m, B, bps).lower(
                p, pool, pool, sds((S, bps), i32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32), sds((S,), i32)),
            "serve_prefill": engine_mod._build_prefill_step(
                m, B, bps).lower(
                p, pool, pool, sds((bps,), i32), sds((1, C), i32),
                sds((), i32), sds((), i32), sds((), f32), sds((), i32)),
        }
        got = {name: hashlib.sha256(low.as_text().encode()).hexdigest()
               for name, low in lowered.items()}
        assert got == self.PINNED
