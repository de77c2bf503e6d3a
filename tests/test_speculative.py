"""Speculative decoding + weight-only int8 decode (DESIGN.md §26).

What this file pins, by class:

- **Accept rule** — ``accept_length`` math in isolation (greedy and
  adversarial prefixes), and the engine-level ledger identity
  ``proposed == accepted + rejected`` per request and in aggregate,
  for both fused draft families.
- **Between steps** — a fused engine is at rest after every step,
  so ``swap_params`` and ``drain`` mid-generation stamp every token
  with the version that sampled it and lose or replay none. (What
  holds for a stream dispatched ahead of its harvest is
  tests/test_serve.py ``TestStepAhead``'s.)
- **KV rollback** — the fused families' pool invariant: rejection
  returns tail blocks via ``trim_blocks`` and
  ``free + Σallocated == total`` holds after EVERY step, fuzzed over
  seeded workloads at temperature 1.0 (low acceptance, max churn).
- **Quantizer** — per-channel int8 error bounds, the 0.25%-of-fp32
  NLL quality bar, fp-path bitwise neutrality of ``qdot``, and the
  Pallas kernel vs the XLA reference contraction.
- **Knobs** — the four-surface convention for TPU_DDP_SPEC_K /
  TPU_DDP_SPEC_DRAFT / TPU_DDP_DECODE_QUANT: env flow into the
  engine, junk rejection at config, coupled-knob violations at the
  engine door, and the retired ``"chain"`` value refused by name on
  every surface.
- **TPOT bugfix** — loadgen inter-token percentiles come from the
  per-token emission stamps (``Request.token_times``), not the old
  uniform (finished-first)/(n-1) estimate that averaged speculative
  bursts away.

Engines here share test_serve's cache geometry (block_size=8,
blocks_per_seq=8 at max_seq_len=64) so the fast tier reuses the same
memoized decode/prefill programs; only the fused spec-step programs
(one per (k, draft_layers, treedef)) compile anew.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.fleet.resilience import continuation_of
from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.ops.quant import (
    QuantizedWeight,
    dequantize,
    nll_drift,
    qdot,
    quantize_params,
    quantize_weight,
)
from tpu_ddp.serve import Request, ServeEngine, run_load
from tpu_ddp.serve.loadgen import RequestSpec
from tpu_ddp.serve.speculative import (
    SPEC_DRAFTS,
    accept_length,
    parse_spec_draft,
)

GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)

# Mixed greedy/sampled workload: (prompt_seed, prompt_len, max_new,
# temperature) — the parity reference covers both sampling regimes.
MIXED = [(0, 5, 6, 0.0), (1, 9, 5, 0.0), (2, 12, 4, 0.7),
         (3, 8, 6, 1.0)]


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


def _submit_mixed(engine):
    return [engine.submit(_prompt(L, seed=ps), n, temperature=t, seed=i)
            for i, (ps, L, n, t) in enumerate(MIXED)]


def _streams(handles):
    return [(list(h.tokens), list(h.logprobs)) for h in handles]


def _ledger_ok(engine, handles) -> bool:
    st = engine.spec_stats()
    return (st["proposed"] == st["accepted"] + st["rejected"]
            and all(h.spec_proposed == h.spec_accepted + h.spec_rejected
                    for h in handles))


# ---------------------------------------------------------------------------
# The accept rule
# ---------------------------------------------------------------------------

class TestAcceptRule:
    def test_full_match_accepts_all(self):
        assert accept_length([5, 6, 7], [5, 6, 7, 9], 3) == 3

    def test_first_mismatch_truncates(self):
        # Draft guessed position 0 wrong: zero proposals accepted,
        # but the engine still emits target column 0 (the token the
        # non-speculative step would have produced).
        assert accept_length([4, 6, 7], [5, 6, 7, 9], 3) == 0

    def test_mismatch_mid_prefix(self):
        assert accept_length([5, 8, 7], [5, 6, 7, 9], 3) == 1

    def test_late_match_does_not_resurrect(self):
        # A correct guess AFTER a wrong one is unusable: the verify
        # column consumed the wrong input, so the prefix rule must
        # not skip over the gap.
        assert accept_length([5, 8, 9], [5, 6, 9, 9], 3) == 1

    @pytest.mark.parametrize("knobs", [
        dict(spec_k=3, spec_draft="self-1"),
        # The quant family's ledger runs the same accept path; its
        # accounting stays in the fast tier via TestKVRollback.
        pytest.param(dict(spec_k=3, spec_draft="quant",
                          decode_quant="int8"),
                     marks=pytest.mark.slow),
    ])
    def test_fused_ledger_identity(self, model, params, knobs):
        """proposed == accepted + rejected, per request and in
        aggregate, and every request still gets its full budget —
        acceptance changes THROUGHPUT, never the emitted stream
        length."""
        eng = ServeEngine(model, params, **GEOM, **knobs)
        hs = _submit_mixed(eng)
        eng.run()
        assert all(h.done for h in hs)
        assert all(len(h.tokens) == n for h, (_, _, n, _) in
                   zip(hs, MIXED))
        assert _ledger_ok(eng, hs)
        st = eng.spec_stats()
        assert st["proposed"] > 0
        assert st["acceptance"] == pytest.approx(
            st["accepted"] / st["proposed"])


# ---------------------------------------------------------------------------
# Between steps: a fused engine is at rest, so swap and drain are exact
# ---------------------------------------------------------------------------

SELF1 = dict(spec_k=3, spec_draft="self-1")


class TestBetweenSteps:
    @staticmethod
    def _midway(engine, handles, n=2):
        while min(len(h.tokens) for h in handles) < n:
            engine.step()
        assert not all(h.done for h in handles)

    def test_swap_params_stamps_the_version_that_sampled(self, model,
                                                         params):
        """swap_params mid-generation on a self-1 engine: a token
        carries the version of the weights that sampled it (one stamp
        per token, bursts included), and what the new weights sample
        is what a fresh engine built on them samples."""
        params2 = model.init(jax.random.key(1))
        same = ServeEngine(model, params, **GEOM, **SELF1)
        ref = _submit_mixed(same)
        same.run()
        eng = ServeEngine(model, params, **GEOM, **SELF1)
        hs = _submit_mixed(eng)
        self._midway(eng, hs)
        had = [len(h.tokens) for h in hs]
        eng.swap_params(params2, version=2)
        late = eng.submit(_prompt(7, seed=4), 5, temperature=0.8, seed=9)
        eng.run()
        for h, r, n0 in zip(hs, ref, had):
            assert h.done and len(h.tokens) == h.max_new_tokens
            assert h.token_versions == [0] * n0 \
                + [2] * (len(h.tokens) - n0)
            # up to the flip: the same program on the same weights
            assert h.tokens[:n0] == r.tokens[:n0]
            assert h.logprobs[:n0] == r.logprobs[:n0]
        assert any(h.tokens != r.tokens for h, r in zip(hs, ref))
        assert set(late.token_versions) == {2}
        fresh = ServeEngine(model, params2, **GEOM, **SELF1)
        want = fresh.submit(_prompt(7, seed=4), 5, temperature=0.8,
                            seed=9)
        fresh.run()
        assert late.tokens == want.tokens
        assert _ledger_ok(eng, hs + [late]) and eng.accounting_ok()

    def test_drain_mid_generation_replays_elsewhere(self, model, params):
        """drain() on a self-1 engine mid-generation, the harvested
        requests resubmitted to a second one: every request ends with
        its whole budget, no token lost or sampled twice, and both
        pools balance."""
        eng = ServeEngine(model, params, **GEOM, **SELF1)
        hs = _submit_mixed(eng)
        self._midway(eng, hs)
        done_here = [h for h in hs if h.done]
        had = {h.rid: len(h.tokens) for h in hs}
        harvested = eng.drain()
        assert [h.rid for h in harvested] \
            == [h.rid for h in hs if h not in done_here]
        assert {h.rid: len(h.tokens) for h in hs} == had
        assert eng.pool.free_count == eng.pool.total_usable
        assert eng.accounting_ok() and not eng.step()
        other = ServeEngine(model, params, **GEOM, **SELF1)
        rests = []
        for h in harvested:
            prompt, left = continuation_of(h)
            assert left == h.max_new_tokens - len(h.tokens)
            rests.append(other.submit(prompt, left,
                                      temperature=h.temperature,
                                      seed=h.seed))
        other.run()
        for h, rest in zip(harvested, rests):
            assert rest.done
            assert len(h.tokens) + len(rest.tokens) == h.max_new_tokens
        assert all(len(h.tokens) == h.max_new_tokens for h in done_here)
        assert other.accounting_ok() and _ledger_ok(other, rests)
        assert _ledger_ok(eng, hs)


# ---------------------------------------------------------------------------
# KV rollback: the fused families' pool invariant
# ---------------------------------------------------------------------------

class TestKVRollback:
    @pytest.mark.parametrize("knobs", [
        dict(spec_k=3, spec_draft="self-1"),
        # self-2 only widens the early-exit depth self-1 already pins.
        pytest.param(dict(spec_k=5, spec_draft="self-2"),
                     marks=pytest.mark.slow),
        dict(spec_k=4, spec_draft="quant", decode_quant="int8"),
    ])
    def test_accounting_holds_after_every_step(self, model, params,
                                               knobs):
        """free + Σallocated == total between ALL steps, not just at
        drain — rejection's trim_blocks rollback can never leak or
        double-free a page."""
        eng = ServeEngine(model, params, **GEOM, **knobs)
        hs = _submit_mixed(eng)
        steps = 0
        while eng.step():
            steps += 1
            assert eng.accounting_ok(), f"pool imbalance at step {steps}"
        assert all(h.done for h in hs)
        assert _ledger_ok(eng, hs)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_rollback_fuzz(self, model, params, seed):
        """Seeded random workloads at temperature 1.0 — the
        lowest-acceptance regime, maximum rollback churn. After the
        drain: full budgets emitted, ledger identity, pool balanced."""
        rng = np.random.default_rng(seed)
        eng = ServeEngine(model, params, **GEOM, spec_k=3,
                          spec_draft="self-1")
        hs = []
        for i in range(8):
            L = int(rng.integers(4, 14))
            n = int(rng.integers(2, 9))
            hs.append(eng.submit(
                rng.integers(0, 1024, size=L, dtype=np.int64), n,
                temperature=1.0, seed=int(rng.integers(0, 2**31 - 1))))
        eng.run()
        assert all(h.done for h in hs)
        assert all(len(h.tokens) == h.max_new_tokens for h in hs)
        assert eng.accounting_ok()
        assert _ledger_ok(eng, hs)

    def test_no_block_leak_across_many_requests(self, model, params):
        """120 requests through one fused engine: the free list ends
        exactly where it started."""
        eng = ServeEngine(model, params, **GEOM, spec_k=2,
                          spec_draft="self-1")
        free0 = eng.pool.free_count
        for i in range(120):
            eng.submit(_prompt(4 + i % 7, seed=i), 1 + i % 5,
                       temperature=float(i % 2), seed=i)
        eng.run()
        assert eng.pool.free_count == free0
        assert eng.accounting_ok()


# ---------------------------------------------------------------------------
# The int8 quantizer and its kernels
# ---------------------------------------------------------------------------

class TestQuantizer:
    def test_roundtrip_error_bound(self):
        """Symmetric per-output-channel int8: reconstruction error is
        at most half a quantization step per column, s_c / 2."""
        rng = np.random.default_rng(0)
        w = rng.normal(size=(64, 48)).astype(np.float32) \
            * rng.uniform(0.01, 10.0, size=(1, 48)).astype(np.float32)
        qw = quantize_weight(jnp.asarray(w))
        assert qw.q.dtype == jnp.int8
        assert int(jnp.max(jnp.abs(qw.q))) <= 127
        err = np.abs(np.asarray(dequantize(qw)) - w)
        bound = np.asarray(qw.s)[None, :] / 2 + 1e-7
        assert (err <= bound).all()

    def test_zero_column_is_exact_and_finite(self):
        w = jnp.zeros((8, 4), jnp.float32)
        qw = quantize_weight(w)
        out = dequantize(qw)
        assert bool(jnp.all(jnp.isfinite(qw.s)))
        assert bool(jnp.all(out == 0))

    def test_reshape_layouts_match_callsites(self):
        # A (d_ff, d_model) wo quantizes through the same (-1, dm)
        # reshape its matmul call site applies.
        w = jnp.asarray(np.random.default_rng(1).normal(
            size=(4, 16, 32)).astype(np.float32))
        qw = quantize_weight(w, reshape=(-1, 32))
        assert qw.shape == (64, 32)

    def test_non_2d_without_reshape_rejected(self):
        with pytest.raises(ValueError, match="2-D matmul layout"):
            quantize_weight(jnp.zeros((2, 3, 4)))

    def test_qdot_fp_path_is_bitwise_neutral(self):
        """For a plain array qdot traces exactly the pre-quantization
        program — fp engines are bitwise unchanged by the refactor."""
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(3, 5, 32)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(32, 2, 24)).astype(np.float32))
        got = qdot(x, w, jnp.float32, reshape=(32, 48))
        want = jnp.dot(x, w.astype(jnp.float32).reshape(32, 48),
                       preferred_element_type=jnp.float32)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want))

    def test_pallas_kernel_matches_xla_reference(self):
        """The Pallas int8 matmul (interpret mode off-TPU) computes
        the same contraction as qdot's XLA reference path — including
        the non-lane-aligned shapes the wrapper pads."""
        from tpu_ddp.ops.pallas.quant_matmul import int8_matmul
        rng = np.random.default_rng(3)
        for m, k, n in [(1, 64, 48), (5, 130, 200), (8, 128, 128)]:
            x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
            qw = quantize_weight(jnp.asarray(
                rng.normal(size=(k, n)).astype(np.float32)))
            got = int8_matmul(x, qw.q, qw.s, interpret=True)
            want = qdot(x, qw, jnp.float32)
            assert got.shape == (m, n)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       rtol=1e-5, atol=1e-5)

    def test_nll_drift_within_quality_bar(self, model, params):
        """The committed bar: quantized decode within 0.25% of fp32
        mean NLL on a seeded eval stream."""
        qparams = quantize_params(model, params)
        rng = np.random.default_rng(3)
        toks = jnp.asarray(
            rng.integers(1, 1024, size=(4, 32)).astype(np.int32))
        d = nll_drift(model, params, qparams, toks)
        assert d["rel_drift"] <= 0.0025
        assert d["greedy_agreement"] >= 0.95
        assert np.isfinite(d["max_abs_logit_err"])

    def test_quantized_tree_is_a_pytree(self, model, params):
        """QuantizedWeight flows through tree ops like a dense leaf
        pair — jit argument passing and donation depend on it."""
        qparams = quantize_params(model, params)
        leaves = jax.tree_util.tree_leaves(qparams)
        assert any(l.dtype == jnp.int8 for l in leaves)
        td1 = jax.tree_util.tree_structure(qparams)
        td2 = jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda x: x, qparams))
        assert td1 == td2
        blk = qparams["blocks"][0]
        assert isinstance(blk["wo"], QuantizedWeight)
        assert blk["ln1"] is params["blocks"][0]["ln1"]  # passthrough


# ---------------------------------------------------------------------------
# Knob surfaces
# ---------------------------------------------------------------------------

class TestKnobs:
    def test_grammar(self):
        assert parse_spec_draft("quant") == ("quant", None)
        assert parse_spec_draft("self-2") == ("self", 2)
        assert parse_spec_draft(" self-1 ") == ("self", 1)
        for junk in ("self-0", "self-x", "self-", "draft", ""):
            with pytest.raises(ValueError, match="spec_draft"):
                parse_spec_draft(junk)
        assert all(parse_spec_draft(s) for s in SPEC_DRAFTS)

    def test_env_defaults_flow_into_engine(self, model, params,
                                           monkeypatch):
        monkeypatch.setenv("TPU_DDP_SPEC_K", "3")
        monkeypatch.setenv("TPU_DDP_SPEC_DRAFT", "self-1")
        monkeypatch.setenv("TPU_DDP_DECODE_QUANT", "int8")
        eng = ServeEngine(model, params, **GEOM)
        assert eng.spec_k == 3
        assert eng.spec_draft == "self-1"
        assert eng.decode_quant == "int8"

    def test_spec_k_alone_builds_the_fused_program(self, model, params):
        """``spec_k`` with nothing else said is real draft-and-verify:
        the default family is ``self-1``."""
        eng = ServeEngine(model, params, **GEOM, spec_k=3)
        assert eng.spec_stats()["spec_draft"] == "self-1"
        assert eng._spec is not None
        assert eng.lower_spec_step() is not None

    @pytest.mark.parametrize("surface", ["parser", "engine", "env",
                                         "launch"])
    def test_chain_is_refused_by_name(self, surface, model, params,
                                      monkeypatch, capsys):
        """The retired schedule fails loudly wherever it can be asked
        for, and the message says what to do instead."""
        if surface == "launch":
            from tpu_ddp import launch
            with pytest.raises(SystemExit):
                launch.main(["part1", "--nproc", "1",
                             "--spec-draft", "chain"])
            said = capsys.readouterr().err
        else:
            with pytest.raises(ValueError) as err:
                if surface == "parser":
                    parse_spec_draft("chain")
                elif surface == "engine":
                    ServeEngine(model, params, **GEOM, spec_k=2,
                                spec_draft="chain")
                else:
                    from tpu_ddp.utils.config import TrainConfig
                    monkeypatch.setenv("TPU_DDP_SPEC_DRAFT", "chain")
                    TrainConfig()
            said = str(err.value)
        assert "'chain' is gone" in said
        assert "spec_k=0 already dispatches" in said
        assert "self-<j>" in said and "quant" in said

    @pytest.mark.parametrize("env,junk,match", [
        ("TPU_DDP_SPEC_K", "-1", "TPU_DDP_SPEC_K"),
        ("TPU_DDP_SPEC_DRAFT", "oracle", "TPU_DDP_SPEC_DRAFT"),
        ("TPU_DDP_DECODE_QUANT", "int3", "TPU_DDP_DECODE_QUANT"),
    ])
    def test_junk_env_rejected(self, env, junk, match, monkeypatch):
        from tpu_ddp.utils.config import TrainConfig
        monkeypatch.setenv(env, junk)
        with pytest.raises(ValueError, match=match):
            TrainConfig()

    def test_coupled_violation_draft_deeper_than_model(self, model,
                                                       params):
        # TransformerLM-tiny has 2 layers: a self-5 draft cannot
        # early-exit past the model's own depth.
        with pytest.raises(ValueError, match="draft depth"):
            ServeEngine(model, params, **GEOM, spec_k=2,
                        spec_draft="self-5")

    def test_negative_spec_k_rejected(self, model, params):
        with pytest.raises(ValueError, match="spec_k"):
            ServeEngine(model, params, **GEOM, spec_k=-1)

    def test_bad_decode_quant_rejected(self, model, params):
        with pytest.raises(ValueError, match="decode_quant"):
            ServeEngine(model, params, **GEOM, decode_quant="int4")

    def test_lower_spec_step_gates(self, model, params):
        """The audit surface exists exactly when a fused program does:
        a k=0 engine has no spec program to lower."""
        eng = ServeEngine(model, params, **GEOM)
        with pytest.raises(ValueError, match="spec_k == 0"):
            eng.lower_spec_step()
        fused = ServeEngine(model, params, **GEOM, spec_k=2,
                            spec_draft="self-1")
        assert fused.lower_spec_step() is not None

    def test_tune_space_carries_spec_knobs(self):
        from tpu_ddp.tune.space import KNOBS, Workload, violations
        names = {k.name for k in KNOBS}
        assert {"spec_k", "spec_draft", "decode_quant"} <= names
        ctx = Workload()
        # Coupled-knob pruning: an inert draft family and a
        # disagg-fleet speculation cell are both rejected.
        assert violations({"spec_draft": "quant", "spec_k": 0}, ctx)
        assert violations({"spec_k": 4, "fleet_roles": "disagg"}, ctx)
        assert violations({"spec_draft": "quant", "spec_k": 4},
                          ctx) == []
        # the default family at spec_k == 0 IS the default cell
        assert violations({"spec_draft": "self-1", "spec_k": 0},
                          ctx) == []
        draft = next(k for k in KNOBS if k.name == "spec_draft")
        assert draft.values == ("self-1", "quant")


# ---------------------------------------------------------------------------
# The TPOT bugfix: percentiles from emission stamps, not uniform math
# ---------------------------------------------------------------------------

class _BurstEngine:
    """Forced-accept stub: completes every request in one step,
    stamping token_times as a BURST — (n-1) near-zero gaps then one
    long inter-burst gap. The old uniform (finished-first)/(n-1)
    estimate reports every gap as the mean and hides the burst; the
    stamped computation must expose both tails."""

    def __init__(self, gap_s=0.1, stamp=True):
        self.gap_s = gap_s
        self.stamp = stamp
        self._pending: list[Request] = []
        self._rid = 0

    def submit(self, prompt, max_new, temperature=0.0, seed=0,
               tenant="default"):
        req = Request(rid=self._rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new),
                      submitted_at=time.perf_counter())
        self._rid += 1
        self._pending.append(req)
        return req

    def step(self):
        if not self._pending:
            return False
        for req in self._pending:
            now = time.perf_counter()
            n = req.max_new_tokens
            # n-1 gaps of 1us (the intra-burst emissions) + one
            # inter-burst gap: bursty by construction.
            stamps = [now + 1e-6 * i for i in range(n - 1)]
            stamps.append(stamps[-1] + self.gap_s)
            req.tokens = list(range(n))
            req.logprobs = [0.0] * n
            req.token_versions = [0] * n
            req.token_times = stamps if self.stamp else []
            req.first_token_at = stamps[0]
            req.finished_at = stamps[-1]
            req.done = True
        self._pending = []
        return True


class TestTPOTFromStamps:
    def test_bursty_stamps_drive_percentiles(self):
        """With 7 near-zero gaps and one 100ms gap per request, the
        stamped p50 is ~0 and the p99 ~100ms; the old uniform
        estimate would have put BOTH at ~12.6ms. This is the loadgen
        regression the speculative burst exposed."""
        eng = _BurstEngine(gap_s=0.1)
        specs = [RequestSpec(prompt=(1, 2, 3), max_new_tokens=9,
                             temperature=0.0, seed=i)
                 for i in range(6)]
        out = run_load(eng, specs, rate=1000.0, seed=0)
        assert out["n_completed"] == 6
        assert out["tpot_p50_ms"] < 1.0          # intra-burst gap
        assert out["tpot_p99_ms"] > 50.0         # inter-burst gap
        # The uniform estimate both gaps would have collapsed to:
        uniform_ms = 0.1 / 8 * 1e3
        assert abs(out["tpot_p50_ms"] - uniform_ms) > 5.0
        assert abs(out["tpot_p99_ms"] - uniform_ms) > 5.0

    def test_stampless_handles_fall_back_to_uniform(self):
        """A handle built outside the engine (no stamps) still weighs
        in via synthetic uniform gaps instead of being dropped: with
        a 0.08s first-to-finish span over 4 gaps, every synthetic gap
        is exactly 20ms."""
        eng = _BurstEngine(gap_s=0.08, stamp=False)
        specs = [RequestSpec(prompt=(1, 2), max_new_tokens=5,
                             temperature=0.0, seed=0)]
        out = run_load(eng, specs, rate=1000.0, seed=0)
        # span = 3 * 1us + 0.08s over n-1 = 4 uniform gaps ≈ 20ms each
        assert out["tpot_p50_ms"] == pytest.approx(20.0, abs=1.0)
        assert out["tpot_p99_ms"] == pytest.approx(20.0, abs=1.0)

    @pytest.mark.parametrize("knobs", [{}, SELF1],
                             ids=["plain", "self-1"])
    def test_real_engine_stamps_every_token(self, model, params, knobs):
        """End to end on the real engine, a step ahead of its harvest
        or speculating in bursts: one stamp per token, stamps
        non-decreasing, and run_load's TPOT fields populate."""
        eng = ServeEngine(model, params, **GEOM, **knobs)
        got, submit = [], eng.submit
        eng.submit = lambda *a, **kw: got.append(submit(*a, **kw)) \
            or got[-1]
        specs = [RequestSpec(prompt=tuple(_prompt(5 + i, seed=i)),
                             max_new_tokens=4 + i, temperature=0.5,
                             seed=i)
                 for i in range(4)]
        out = run_load(eng, specs, rate=1000.0, seed=1)
        assert out["n_completed"] == 4
        for h in got:
            assert len(h.token_times) == len(h.tokens) == h.max_new_tokens
            assert h.token_times == sorted(h.token_times)
        assert out["tpot_p50_ms"] is not None
        assert out["tpot_p99_ms"] >= out["tpot_p50_ms"]
