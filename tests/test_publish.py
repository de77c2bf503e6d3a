"""Live train→serve weight streaming (tpu_ddp/publish/, DESIGN.md §24):
the versioned store's monotonic/rollback contract, wire exactness and
byte reductions, the zero-copy no-retrace version flip, atomic cutover
(token-level parity across a mid-request flip), the staleness gate and
chaos drills, and the closed online-RL round trip where the engine
provably serves trainer-updated weights.

Engines share the fast-tier cache geometry (tests/test_serve.py), so
the memoized decode/prefill programs compile once for the module.
"""

import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.publish import (
    PUBLISH_WIRES,
    Publisher,
    StaleVersionError,
    Subscriber,
    VersionedParams,
    attach,
    tree_digests,
)
from tpu_ddp.publish.subscriber import _APPLY
from tpu_ddp.serve import ServeEngine
from tpu_ddp.utils.profiling import SERVE_DECODE, SERVE_PREFILL

GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def model():
    return make_transformer("TransformerLM-tiny", max_seq_len=64,
                            compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(0))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _perturb(tree, eps):
    return jax.tree.map(lambda x: x + np.float32(eps), tree)


def _drain(engine, sub, cap=200):
    for _ in range(cap):
        if not sub.lag:
            return
        engine.step()
    raise AssertionError(f"subscriber still lagging after {cap} steps")


def _state(tree, step):
    return types.SimpleNamespace(params=tree, step=step)


class TestVersionedStore:
    def test_commit_is_strictly_monotonic(self):
        tree = {"w": np.ones(4, np.float32)}
        store = VersionedParams(tree)
        assert store.version == 0 and store.verify()
        nxt = {"w": np.full(4, 2.0, np.float32)}
        store.commit(nxt, 1, nxt)
        assert store.version == 1 and store.last_good_version == 0
        for bad in (1, 0, -3):
            with pytest.raises(StaleVersionError):
                store.commit(nxt, bad, nxt)

    def test_rollback_restores_last_good(self):
        v0 = {"w": np.arange(4, dtype=np.float32)}
        store = VersionedParams(v0)
        d0 = store.digests
        v1 = {"w": np.arange(4, dtype=np.float32) + 1}
        store.commit(v1, 1, v1)
        version, host = store.rollback()
        assert version == 0
        np.testing.assert_array_equal(host["w"], v0["w"])
        assert store.digests == d0 and store.verify()
        with pytest.raises(ValueError):
            store.rollback()   # retention is one-deep


class TestWire:
    def test_full_push_is_exact_and_digests_agree(self, model, params):
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        sub = attach(pub, eng, name="w")[0]
        update = pub.publish(params=params, step=1)
        assert update.kind == "full" and update.version == 1
        _drain(eng, sub)
        # f32 through the dense wire is exact: the served tree is
        # bitwise the published one, on device and in the host mirror.
        assert tree_digests(_host(eng.params)) == update.digests
        assert sub.store.digests == update.digests
        assert eng.param_version == 1

    def test_delta_trajectory_tracks_and_stays_bitwise_synced(
            self, model, params):
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        sub = attach(pub, eng, name="d")[0]
        pub.publish(params=params, step=0)
        p = params
        for step in range(1, 4):
            p = _perturb(p, 0.01)
            update = pub.publish(params=p, step=step)
            assert update.kind == "delta"
            _drain(eng, sub)
            # Bitwise publisher<->subscriber at every version...
            assert sub.store.digests == update.digests
            assert tree_digests(_host(eng.params)) == update.digests
        # ...and the reconstruction tracks the raw trajectory (exact
        # equality is not owed — a+(b-a) != b in floats — closeness is).
        for a, b in zip(jax.tree.leaves(sub.store.host),
                        jax.tree.leaves(_host(p))):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)

    def test_lossy_wires_cut_bytes_in_order(self, params):
        host = _host(params)
        sent = {}
        for wire in PUBLISH_WIRES:
            pub = Publisher(publish_every=1, wire=wire, bucket_mb=1)
            pub.publish(params=host, step=0)
            for c in pub._codecs:
                c.reset()          # count the delta trajectory only
            p = host
            for step in range(1, 4):
                p = _perturb(p, 0.001)
                pub.publish(params=p, step=step)
                sent[wire] = pub.stats()["bytes_sent"]
        assert sent["int8"] < sent["bf16"] < sent["none"]

    def test_int8_error_feedback_stays_synced_and_close(
            self, model, params):
        """The lossy wire's contract: publisher reconstruction and
        subscriber land bitwise equal at every version (reconstruction
        tracking), and error feedback keeps the served weights close
        to the raw trained trajectory instead of drifting."""
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="int8", bucket_mb=1)
        sub = attach(pub, eng, name="ef")[0]
        pub.publish(params=params, step=0)
        p = params
        for step in range(1, 5):
            p = _perturb(p, 0.001)
            u = pub.publish(params=p, step=step)
            _drain(eng, sub)
            assert sub.store.digests == u.digests
        raw = _host(p)
        for a, b in zip(jax.tree.leaves(sub.store.host),
                        jax.tree.leaves(raw)):
            np.testing.assert_allclose(a, b, rtol=0, atol=5e-3)

    def test_layout_change_forces_full_push(self, model, params):
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        assert pub.publish(params=params, step=0).kind == "full"
        assert pub.publish(params=params, step=1).kind == "delta"
        other = {"w": np.ones((8, 8), np.float32)}
        assert pub.publish(params=other, step=2).kind == "full"


class TestAtomicSwap:
    def test_flip_does_not_retrace_or_copy(self, model, params,
                                           no_retrace):
        from tpu_ddp.analysis import (donation_report,
                                      runtime_donation_check)

        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        sub = attach(pub, eng, name="nr")[0]
        # Warm every program: full push + one delta flip + a request.
        pub.publish(params=params, step=0)
        _drain(eng, sub)
        pub.publish(params=_perturb(params, 0.01), step=1)
        _drain(eng, sub)
        r = eng.submit([1, 2, 3], 2)
        eng.run()
        # Steady state: further version flips reuse every executable.
        with no_retrace(0, watch=("push_pack", "apply_delta",
                                  SERVE_DECODE, SERVE_PREFILL)):
            p = _perturb(params, 0.02)
            for step in range(2, 5):
                pub.publish(params=p, step=step)
                _drain(eng, sub)
                p = _perturb(p, 0.01)
            r = eng.submit([4, 5, 6], 2)
            eng.run()
        assert eng.param_version == 5 and r.done
        # Static donation claim: the staging->live apply aliases the
        # donated live tree (an unaliased donation = full-model copy
        # every flip).
        rep = donation_report(sub.lower_apply_step(), min_bytes=1024)
        assert rep["findings"] == []
        assert rep["donated"], "apply donates nothing?"
        # Runtime claim: the donated buffers are actually REUSED.
        # (jnp.array copy=True: a CPU jnp.asarray of host numpy may
        # alias the numpy buffer, which XLA then cannot donate.)
        live = jax.tree.map(lambda x: jnp.array(x, copy=True),
                            _host(params))
        delta = jax.tree.map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), live)
        findings = runtime_donation_check(_APPLY, live, delta,
                                          min_bytes=1024)
        assert findings == []

    def test_foreign_layout_is_rejected_before_any_flip(self, model,
                                                        params):
        """An update whose bucket layout does not match the engine's
        parameters is dropped loudly — the engine keeps serving."""
        eng = ServeEngine(model, params, **GEOM)
        sub = Subscriber(eng, name="fl")
        other_pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        u = other_pub.publish(params={"w": np.ones((8, 8), np.float32)},
                              step=0)
        sub.deliver(u)
        with pytest.warns(UserWarning, match="layout"):
            sub.on_engine_step()
        assert sub.rejected == 1 and sub.applied_version == 0
        r = eng.submit([1, 2, 3], 2)
        eng.run()
        assert r.done


class TestAtomicCutover:
    def test_token_parity_across_mid_request_flip(self, model, params):
        """A request overlapping the flip is bitwise identical to the
        runs on the versions each token saw: tokens before the flip
        match the v1 run, tokens after match the v2 continuation, and
        the stamps split exactly [v1]*j + [v2]*(n-j)."""
        prompt = np.arange(1, 7, dtype=np.int64)
        n_new, j = 8, 3
        kw = dict(temperature=0.7, seed=11)

        eng = ServeEngine(model, params, **GEOM)
        # bucket_mb big enough for a single bucket: the flip lands on
        # the first engine step after the publish, deterministically.
        pub = Publisher(publish_every=1, wire="none", bucket_mb=64)
        sub = attach(pub, eng, name="cut")[0]
        pub.publish(params=params, step=0)     # v1 == params (f32 exact)
        _drain(eng, sub)

        # Reference run entirely on v1.
        ref1 = ServeEngine(model, params, **GEOM)
        r1 = ref1.submit(prompt, n_new, **kw)
        ref1.run()

        # The spanning request: j tokens on v1, then the flip.
        rc = eng.submit(prompt, n_new, **kw)
        while len(rc.tokens) < j:
            eng.step()
        # The engine runs a step ahead of its readback: the token in
        # flight was dispatched on v1 too, and run() reads it back.
        eng.run(max_steps=0)
        j = len(rc.tokens)
        assert 3 <= j < n_new
        pub.publish(params=_perturb(params, 0.01), step=1)   # v2
        eng.run()
        assert rc.done and len(rc.tokens) == n_new
        assert rc.token_versions == [1] * j + [2] * (n_new - j)

        # Prefix parity: what v1 served is what the v1-only run sampled.
        assert rc.tokens[:j] == r1.tokens[:j]
        # Tail parity: continuation on the v2 weights (the engine's own
        # post-flip tree — bitwise what the subscriber committed), with
        # the stateless (seed, position) sampling contract.
        ref2 = ServeEngine(model, sub.store.host, **GEOM)
        r2 = ref2.submit(np.concatenate([prompt.astype(np.int32),
                                         np.asarray(rc.tokens[:j],
                                                    np.int32)]),
                         n_new - j, **kw)
        ref2.run()
        assert rc.tokens[j:] == r2.tokens
        # Deterministic replay: the same spanning run replays bitwise.
        eng2 = ServeEngine(model, params, **GEOM)
        pub2 = Publisher(publish_every=1, wire="none", bucket_mb=64)
        sub2 = attach(pub2, eng2, name="cut2")[0]
        pub2.publish(params=params, step=0)
        _drain(eng2, sub2)
        rr = eng2.submit(prompt, n_new, **kw)
        while len(rr.tokens) < 3:
            eng2.step()
        eng2.run(max_steps=0)
        pub2.publish(params=_perturb(params, 0.01), step=1)
        eng2.run()
        assert rr.tokens == rc.tokens
        assert rr.token_versions == rc.token_versions

    def test_loadgen_asserts_cutover_and_reports_versions(
            self, model, params):
        from tpu_ddp.serve.loadgen import (assert_atomic_cutover,
                                           make_workload, run_load)

        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=64)
        sub = attach(pub, eng, name="lg")[0]
        pub.publish(params=params, step=0)
        _drain(eng, sub)
        specs = make_workload(6, 1024, seed=3)
        metrics = run_load(eng, specs, rate=500.0, seed=3)
        assert metrics["param_version_min"] == 1
        assert metrics["param_version_max"] == 1
        assert metrics["n_version_spanning"] == 0
        # A decreasing stamp sequence is the bug the assert exists for.
        bad = types.SimpleNamespace(rid=9, tokens=[1, 2],
                                    token_versions=[2, 1])
        with pytest.raises(AssertionError):
            assert_atomic_cutover([bad])
        short = types.SimpleNamespace(rid=9, tokens=[1, 2],
                                      token_versions=[1])
        with pytest.raises(AssertionError):
            assert_atomic_cutover([short])


class TestStalenessAndChaos:
    def test_gate_blocks_then_catches_up(self, model, params):
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none",
                        max_staleness_steps=1, bucket_mb=1)
        sub = attach(pub, eng, name="g")[0]
        p = params
        for step in range(1, 6):
            p = _perturb(p, 0.01)
            pub.after_step(_state(p, step), step)
        # The gate pumped the attached engine: staleness is bounded...
        assert pub.staleness(5) <= pub.max_staleness_steps
        assert pub.gate_blocks >= 1
        # ...and a drain converges to the final version, nothing lost.
        _drain(eng, sub)
        assert eng.param_version == pub.version == 5
        assert sub.rejected == 0

    def test_publisher_death_keeps_serving_last_good(
            self, model, params, monkeypatch, tmp_path):
        monkeypatch.setenv("TPU_DDP_CHAOS_FAULTS", "publisher-death@2")
        monkeypatch.setenv("TPU_DDP_CHAOS_SENTINEL", str(tmp_path))
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        sub = attach(pub, eng, name="pd")[0]
        assert pub.publish(params=params, step=1) is not None
        _drain(eng, sub)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            u2 = pub.publish(params=_perturb(params, 0.5), step=2)
        assert u2 is None and pub.dead and pub.deaths == 1
        assert sub.publisher_lost_n == 1
        assert any("publisher lost" in str(x.message) for x in w)
        # Serving survives on the last-good version, and says so.
        r = eng.submit([1, 2, 3], 3)
        eng.run()
        assert r.done and eng.param_version == 1
        assert r.token_versions == [1, 1, 1]
        # The cadence respects death: no further pushes are attempted.
        assert pub.maybe_publish(_state(params, 3), 3) is None

    def test_push_stall_delays_in_order_and_gates(
            self, model, params, monkeypatch, tmp_path):
        monkeypatch.setenv("TPU_DDP_CHAOS_FAULTS", "push-stall@2")
        monkeypatch.setenv("TPU_DDP_CHAOS_SENTINEL", str(tmp_path))
        eng = ServeEngine(model, params, **GEOM)
        pub = Publisher(publish_every=1, wire="none",
                        max_staleness_steps=1, bucket_mb=1)
        sub = attach(pub, eng, name="st")[0]
        p = params
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for step in range(1, 5):
                p = _perturb(p, 0.01)
                pub.after_step(_state(p, step), step)
        assert pub.stalls == 1
        assert pub.stall_events == 1 and not pub._stalled
        assert any("stalled" in str(x.message) for x in w)
        # Order held through the stall: nothing rejected, and the
        # engine converges bitwise to the final published version.
        assert sub.rejected == 0
        _drain(eng, sub)
        assert eng.param_version == pub.version == 4
        assert tree_digests(_host(eng.params)) == sub.store.digests


class TestRouterFanout:
    def test_one_publish_reaches_every_replica(self, model, params):
        from tpu_ddp.fleet import Router

        replicas = [ServeEngine(model, params, **GEOM)
                    for _ in range(2)]
        router = Router(replicas)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=1)
        subs = router.subscribe(pub)
        assert len(subs) == 2 and len(pub.subscribers) == 2
        pub.publish(params=_perturb(params, 0.01), step=1)
        for _ in range(200):
            if not any(s.lag for s in subs):
                break
            router.step()
        assert all(r.param_version == 1 for r in replicas)
        d = {tuple(s.store.digests) for s in subs}
        assert len(d) == 1, "replicas diverged"
        for s in router.stats()["replicas"]:
            assert s["param_version"] == 1 and s["publish_lag"] == 0


class TestKnobs:
    def test_env_junk_is_rejected_by_name(self, monkeypatch):
        from tpu_ddp.utils.config import TrainConfig

        for env, junk in (("TPU_DDP_PUBLISH_EVERY", "soon"),
                          ("TPU_DDP_PUBLISH_EVERY", "-2"),
                          ("TPU_DDP_PUBLISH_WIRE", "zstd"),
                          ("TPU_DDP_PUBLISH_MAX_STALENESS", "lots"),
                          ("TPU_DDP_PUBLISH_MAX_STALENESS", "-1")):
            monkeypatch.setenv(env, junk)
            with pytest.raises(ValueError, match=env):
                TrainConfig()
            monkeypatch.delenv(env)

    def test_env_reaches_publisher_defaults(self, monkeypatch):
        from tpu_ddp.utils.config import TrainConfig

        monkeypatch.setenv("TPU_DDP_PUBLISH_EVERY", "4")
        monkeypatch.setenv("TPU_DDP_PUBLISH_WIRE", "int8")
        monkeypatch.setenv("TPU_DDP_PUBLISH_MAX_STALENESS", "2")
        pub = Publisher(config=TrainConfig())
        assert (pub.publish_every, pub.wire,
                pub.max_staleness_steps) == (4, "int8", 2)

    def test_publisher_mirrors_config_validation(self):
        with pytest.raises(ValueError):
            Publisher(publish_every=-1)
        with pytest.raises(ValueError):
            Publisher(wire="zstd")
        with pytest.raises(ValueError):
            Publisher(max_staleness_steps=-1)

    def test_inert_combinations_are_tune_violations(self):
        from tpu_ddp.tune.space import Workload, violations

        ctx = Workload()
        assert violations({"publish_every": 0, "publish_wire": "bf16"},
                          ctx)
        assert violations({"publish_every": 0,
                           "max_staleness_steps": 2}, ctx)
        assert not violations({"publish_every": 4,
                               "publish_wire": "bf16",
                               "max_staleness_steps": 2}, ctx)


class TestClosedLoop:
    def test_engine_provably_serves_trainer_updated_weights(self):
        """The round trip the subsystem exists for: generate → score →
        train → publish, with the served tree bitwise pinned to the
        publisher's reconstruction at every round."""
        from tpu_ddp.ops.optim import SGD
        from tpu_ddp.parallel.mesh import make_mesh
        from tpu_ddp.publish.rollout import make_prompts, run_online_loop
        from tpu_ddp.train.lm import LMTrainer

        model = make_transformer("TransformerLM-tiny", max_seq_len=64,
                                 compute_dtype=jnp.float32)
        mesh = make_mesh(jax.devices()[:2], dp=2)
        trainer = LMTrainer(model, mesh,
                            optimizer=SGD(learning_rate=0.1,
                                          momentum=0.9))
        state = trainer.init_state(seed=3)
        host0 = trainer.params_to_host(state)
        engine = ServeEngine(model, host0, **GEOM)
        d0 = tree_digests(host0)

        pub = Publisher(trainer, publish_every=1, wire="none",
                        bucket_mb=1)
        sub = attach(pub, engine, name="rl")[0]
        prompts = make_prompts(2, 1024, prompt_len=6, seed=0)
        state, report = run_online_loop(
            trainer, engine, pub, state, rounds=2, prompts=prompts,
            max_new_tokens=6, temperature=0.8, samples_per_prompt=2,
            settle_steps=40)
        # Versions advanced and the engine caught up.
        assert pub.version == 2
        assert engine.param_version == 2 and sub.lag == 0
        # The engine serves EXACTLY what the trainer published: equal
        # digests on device params, subscriber mirror, and publisher
        # reconstruction.
        served = tree_digests(_host(engine.params))
        assert served == sub.store.digests
        assert served == tree_digests(
            jax.tree.unflatten(pub._treedef, pub._last))
        # And they are genuinely NEW weights, close to the live state.
        assert served != d0
        for a, b in zip(pub._last,
                        jax.tree.leaves(trainer.params_to_host(state))):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
        assert report["rounds"][-1]["published_version"] == 2
