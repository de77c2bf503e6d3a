"""An attention-free model on the serving path (``RetentionLM``: gated
degree-2 power retention in every layer, a fixed-size state per sequence
and no K/V) against the plain reference (benchmark/reference/brumby.py,
the quadratic form), on the CPU at a small size with seeded random
weights: log-probabilities through chunked prefill and decode, the
symmetric power, the chunked form against the one-token recurrence, the
three rules of the state pool (each with a run that breaks it), a pool
with no K/V layer, every serving feature that refuses such a model, and
the other serving models' two programs traced as they were before this
model came.
"""

import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.reference import brumby as reference  # noqa: E402
from tpu_ddp.models import decode, retention  # noqa: E402
from tpu_ddp.models.retention import (  # noqa: E402
    RetentionLM, retention_chunk, retention_step, sympow2)
from tpu_ddp.serve import ServeEngine  # noqa: E402
from tpu_ddp.serve import engine as engine_mod  # noqa: E402

# blocks of 4 under chunks of 16: prompts of 40-70 tokens are 3-5 chunks
GEOM = dict(num_slots=3, block_size=4, prefill_chunk=16)
TOL = 2e-5      # float32 on both sides: the two forms sum in other orders


def _model(**kw):
    cfg = dict(vocab_size=512, num_layers=2, num_heads=4, num_kv_heads=2,
               head_dim_=16, d_model=64, d_ff=128, max_seq_len=128,
               compute_dtype=jnp.float32)
    cfg.update(kw)
    return RetentionLM(**cfg)


def _cfg(model) -> dict:
    """The reference's side of ``model``: the keys it reads."""
    return {"rms_norm_eps": model.norm_eps, "rope_theta": model.rope_theta,
            "power_retention": {"eps": model.retention_eps}}


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(7))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 512, size=n)


def _worst(model, params, prompt, req) -> float:
    """Largest difference between the log-probabilities the engine
    reported for ``req`` and the reference's full forward pass."""
    full = np.concatenate([prompt, req.tokens]).astype(np.int32)
    ref = np.asarray(reference.log_probs(params, jnp.asarray(full[:-1]),
                                         _cfg(model)))
    at = np.arange(len(prompt) - 1, len(full) - 1)
    return float(np.max(np.abs(ref[at, full[at + 1]]
                               - np.asarray(req.logprobs))))


# Prompts of three to five chunks, with and without padding in the last;
# five requests on three slots, so slots turn over and a long prompt is
# between two chunks while its neighbours decode.
CASES = [(40, 6), (55, 9), (70, 5), (48, 7), (64, 4)]


def _serve(model, params, cases=CASES, **kw):
    eng = ServeEngine(model, params, **dict(GEOM, **kw))
    prompts = [_prompt(n, seed=i) for i, (n, _) in enumerate(cases)]
    reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, cases)]
    eng.run()
    return eng, prompts, reqs


# ---- (a) the engine against the reference ----------------------------------------

def test_engine_logprobs_match_the_reference(model, params):
    eng, prompts, reqs = _serve(model, params)
    for p, r, (_, new) in zip(prompts, reqs, CASES):
        assert r.done and len(r.tokens) == new
        assert _worst(model, params, p, r) < TOL
    assert eng.pool.free_count == eng.pool.total_usable


def test_bf16_compute_keeps_the_state_in_float32():
    """The serving default (bf16 projections and MLP) against the float32
    reference: within 0.05 at these widths (bf16 rounding of two layers'
    inputs), and the state pool is float32 whatever the compute dtype."""
    m = _model(compute_dtype=jnp.bfloat16)
    p = m.init(jax.random.key(3))
    eng, prompts, reqs = _serve(m, p, CASES[:3])
    assert {a.dtype for a in eng.state.arrays.values()} == {
        jnp.dtype(jnp.float32)}
    for prompt, r in zip(prompts, reqs):
        assert _worst(m, p, prompt, r) < 0.05


# ---- (b) the symmetric power ------------------------------------------------------

@pytest.mark.parametrize("d", [1, 2, 16, 128])
def test_the_symmetric_power_gives_the_square_of_the_dot(d):
    u, w = (jax.random.normal(jax.random.key(s), (5, d)) for s in (0, 1))
    phi_u, phi_w = sympow2(u), sympow2(w)
    assert phi_u.shape == (5, d * (d + 1) // 2) == (5, retention.features(d))
    # float32: a sum of d (d + 1) / 2 products against a square of d
    np.testing.assert_allclose(np.sum(phi_u * phi_w, -1),
                               np.sum(u * w, -1) ** 2, rtol=2e-5, atol=1e-5)
    # half the full tensor power's features for the same numbers
    full = (u[:, :, None] * u[:, None, :]).reshape(5, -1)
    np.testing.assert_allclose(np.sum(phi_u ** 2, -1),
                               np.sum(full ** 2, -1), rtol=2e-5)


# ---- (c) the chunked form against the one-token recurrence ------------------------

def _run(model, n, seed):
    """Random q (n, H, hd), k, v (n, KV, hd) and log decays a (n, KV)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    H, KV, hd = model.num_heads, model.kv_heads, model.head_dim
    return (jax.random.normal(ks[0], (n, H, hd)),
            jax.random.normal(ks[1], (n, KV, hd)),
            jax.random.normal(ks[2], (n, KV, hd)),
            jax.nn.log_sigmoid(2.0 + jax.random.normal(ks[3], (n, KV))))


def _steps(model, q, k, v, a, S, z):
    """The one-token recurrence over the run: (o (n, H, hd), S, z)."""
    outs = []
    for t in range(q.shape[0]):
        o, S, z = retention_step(model, q[t:t + 1], k[t:t + 1], v[t:t + 1],
                                 a[t:t + 1], S, z)
        outs.append(o[0])
    return jnp.stack(outs), S, z


def _chunks(model, q, k, v, a, S, z, c):
    """The chunked form over pieces of ``c``, the last padded (gate 1,
    zero key and value) where ``c`` does not divide the run."""
    n = q.shape[0]
    outs = []
    for i in range(0, n, c):
        m = min(c, n - i)
        pad = [(0, c - m)] + [(0, 0)] * 2
        o, S, z = retention_chunk(
            model, jnp.pad(q[i:i + m], pad), jnp.pad(k[i:i + m], pad),
            jnp.pad(v[i:i + m], pad), jnp.pad(a[i:i + m], pad[:2]), S, z)
        outs.append(o[:m])
    return jnp.concatenate(outs), S, z


@pytest.mark.parametrize("c", [8, 24, 5, 7])
@pytest.mark.parametrize("incoming", ["zero", "carried"])
def test_the_chunked_form_is_the_one_token_recurrence(model, c, incoming):
    F = model.feature_dim
    S = jnp.zeros((1, model.kv_heads, F, model.head_dim))
    z = jnp.zeros((1, model.kv_heads, F))
    if incoming == "carried":       # a state some 30 positions made
        _, S, z = _steps(model, *_run(model, 30, seed=11), S, z)
    q, k, v, a = _run(model, 24, seed=5)
    want, S1, z1 = _steps(model, q, k, v, a, S, z)
    got, S2, z2 = _chunks(model, q, k, v, a, S[0], z[0], c)
    # float32 both: products of decays against exponents of sums, and
    # sums of up to 54 terms of either sign in other orders
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S2, S1[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(z2, z1[0], rtol=1e-4, atol=1e-4)


def test_a_chunk_in_parts_gives_what_it_gives_whole(model, monkeypatch):
    """Parts of queries for the carry and of keys for the update (here 8
    and 4 of a chunk of 16) change the order of no sum that matters."""
    S = jax.random.normal(jax.random.key(1),
                          (model.kv_heads, model.feature_dim,
                           model.head_dim))
    z = jnp.abs(jax.random.normal(jax.random.key(2),
                                  (model.kv_heads, model.feature_dim)))
    run = _run(model, 16, seed=3)
    whole = retention_chunk(model, *run, S, z)
    row = 4 * model.num_heads * model.feature_dim
    monkeypatch.setattr(retention, "PART_BYTES", 2 * row)
    assert retention._parts(16, row) == 8
    assert retention._parts(16, 4 * model.kv_heads * model.feature_dim) == 4
    for a, b in zip(retention_chunk(model, *run, S, z), whole):
        # float32: the fold's sums, part by part, in another order
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---- (d) the three rules of the state pool ----------------------------------------

def test_an_idle_slot_is_left_bit_for_bit(model, params):
    """Slots 1 and 2 hold a state no request owns while slot 0 prefills
    and decodes: not one bit of theirs moves."""
    eng = ServeEngine(model, params, **GEOM)
    noise = {k: jax.random.normal(jax.random.key(5), a.shape, a.dtype)
             for k, a in eng.state.arrays.items()}
    eng.state.arrays = {k: a.at[:, 1:].set(noise[k][:, 1:])
                        for k, a in eng.state.arrays.items()}
    before = {k: np.asarray(a[:, 1:]) for k, a in eng.state.arrays.items()}
    eng.submit(_prompt(40, seed=1), 6)
    eng.run()
    for k, a in eng.state.arrays.items():
        assert np.array_equal(np.asarray(a[:, 1:]).view(np.uint32),
                              before[k].view(np.uint32)), k


def _break_untouched(monkeypatch):
    """Rule 1 off: the decode step advances every slot's state."""
    real = engine_mod.retention_state_step
    monkeypatch.setattr(
        engine_mod, "retention_state_step",
        lambda model, blk, x, state, si, active, pos: real(
            model, blk, x, state, si, jnp.ones_like(active), pos))


def _break_fresh(monkeypatch):
    """Rule 2 off: a first chunk starts from what the slot holds."""
    real = engine_mod.retention_state_chunk
    monkeypatch.setattr(
        engine_mod, "retention_state_chunk",
        lambda model, blk, x, state, si, slot, fresh, n_valid, pos: real(
            model, blk, x, state, si, slot, False, n_valid, pos))


def _break_padding(monkeypatch):
    """Rule 3 off: the padding of a final chunk advances the state."""
    real = engine_mod.retention_state_chunk
    monkeypatch.setattr(
        engine_mod, "retention_state_chunk",
        lambda model, blk, x, state, si, slot, fresh, n_valid, pos: real(
            model, blk, x, state, si, slot, fresh, x.shape[1], pos))


# rule -> (how to break it, the requests that show it, the one to look at)
RULES = {
    # a prompt of four chunks on slot 1 while slot 0 decodes
    "untouched_between_chunks": (_break_untouched, [(4, 12), (60, 4)], 1),
    # slot 0: a long tenant, then a shorter one
    "zero_at_the_first_chunk": (_break_fresh, [(45, 3), (20, 5)], 1),
    # 37 tokens in chunks of 16: eleven rows of padding
    "padding_does_not_advance": (_break_padding, [(37, 6)], 0),
}


@pytest.mark.parametrize("rule", sorted(RULES))
@pytest.mark.parametrize("broken", [False, True])
def test_each_state_rule_holds_and_is_needed(model, params, monkeypatch,
                                             rule, broken):
    breaker, cases, look = RULES[rule]
    slots = 1 if rule == "zero_at_the_first_chunk" else 2
    if broken:
        breaker(monkeypatch)
    engine_mod._build_decode_step.cache_clear()
    engine_mod._build_prefill_step.cache_clear()
    try:
        _, prompts, reqs = _serve(model, params, cases, num_slots=slots)
    finally:
        engine_mod._build_decode_step.cache_clear()
        engine_mod._build_prefill_step.cache_clear()
    worst = _worst(model, params, prompts[look], reqs[look])
    if broken:
        assert worst > 10 * TOL, f"{rule} is not needed? {worst}"
    else:
        assert worst < TOL


def test_the_counts_of_carried_chunks(model, params):
    """40 tokens in chunks of 16: three chunks, the last two carried."""
    eng = ServeEngine(model, params, **dict(GEOM, num_slots=1))
    eng.submit(_prompt(40, seed=9), 3)
    eng.submit(_prompt(12, seed=8), 2)
    eng.run()
    tally = eng._tally()
    assert tally["prefill_chunks"] == 4
    assert tally["prefill_state_carried"] == 2
    assert eng.metrics.gauges["serve_prefill_state_carried"]["count"] == 4


# ---- (e) a pool with no K/V layer ---------------------------------------------------

def test_a_model_without_kv_layers_allocates_no_pages(model, params):
    eng = ServeEngine(model, params, **GEOM)
    assert eng.pool.layers == 0 and eng.pool.nbytes == 0
    assert eng.pool.k.size == eng.pool.v.size == 0
    assert eng.pool.index is None
    # S (KV, F, hd) and z (KV, F) a layer and slot, float32
    F = model.feature_dim
    assert eng.state.nbytes == (model.num_layers * GEOM["num_slots"]
                                * model.kv_heads * F * (model.head_dim + 1)
                                * 4)
    assert eng.metrics.gauges["serve_kv_pool_bytes"]["last"] == 0


def test_more_slots_than_the_blocks_would_allow_are_not_refused(model,
                                                                 params):
    """Blocks cost nothing where no layer keeps K/V: four slots of 64
    positions at blocks of 4 need 64 blocks, 9 are asked for, and all
    four requests are admitted at once and served."""
    eng = ServeEngine(model, params, **dict(GEOM, num_slots=4),
                      num_blocks=9)
    assert eng.pool.total_usable >= 4 * eng.blocks_per_seq
    cases = [(56, 6), (50, 5), (60, 4), (45, 6)]
    reqs = [eng.submit(_prompt(n, seed=20 + i), new)
            for i, (n, new) in enumerate(cases)]
    eng.step()
    assert sum(s is not None for s in eng.sched.slots) == 4
    eng.run()
    assert all(r.done and len(r.tokens) == new
               for r, (_, new) in zip(reqs, cases))


def test_programs_donate_the_state_and_not_the_empty_pool(model, params):
    """The two state arrays are donated, the pool's arrays of 0 layers
    are not; the scopes of the mixer are where a reader looks for them."""
    eng = ServeEngine(model, params, **GEOM)
    for lower in (eng.lower_decode_step, eng.lower_prefill_step):
        text = lower().as_text()
        main = next(ln for ln in text.splitlines()
                    if "func.func public @main" in ln).split(") -> ")[0]
        donated = re.findall(
            r"tensor<([0-9x]+)x[a-z0-9]+> \{[^%]*(?:tf\.aliasing_output|"
            r"jax\.buffer_donor)", main)
        assert sorted(donated) == sorted(
            "x".join(map(str, a.shape)) for a in eng.state.arrays.values())
    text = eng.lower_decode_step().as_text(debug_info=True)
    assert "jit(serve_decode)/retention/state/" in text
    text = eng.lower_prefill_step().as_text(debug_info=True)
    for scope in ("retention/carry", "retention/chunk"):
        assert f"jit(serve_prefill)/{scope}/" in text, scope


# ---- (f) what refuses --------------------------------------------------------------

@pytest.mark.parametrize("kw,reason", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_tiers=2, hbm_blocks=9, cold_blocks=9), "kv_tiers"),
    (dict(spec_k=2), "spec_k"),
    (dict(decode_quant="int8"), "decode_quant"),
    (dict(cp_prefill="ring", mesh="sp2"), "cp_prefill"),
    (dict(mesh="sp2"), "mesh"),
])
def test_features_that_cannot_carry_state_refuse_with_the_reason(
        model, params, kw, reason):
    if kw.get("mesh") == "sp2":
        from tpu_ddp.parallel.mesh import make_mesh
        kw = dict(kw, mesh=make_mesh(jax.devices()[:2], dp=1, sp=2))
    with pytest.raises(ValueError, match="keeps recurrent state") as e:
        ServeEngine(model, params, **dict(GEOM, **kw))
    assert f"{reason} (" in str(e.value)
    assert decode._STATE_REFUSALS[reason] in str(e.value)


def test_disagg_refuses_with_the_reason(model, params):
    from tpu_ddp.fleet.disagg import DisaggEngine
    with pytest.raises(ValueError, match=r"disagg \(the edge ships"):
        DisaggEngine(model, params, **GEOM)


# ---- (g) the other serving models' programs, as they were ----------------------------

# ``serve_decode`` and ``serve_prefill`` of each other serving cell's
# model at its geometry, lowered from shapes alone (no source locations
# in the text), hashed on the parent of the change that brought the
# retention model (which shares ``_keep`` / ``_slot_state`` with the
# hybrid's state layers, donates by ``_donated`` and sizes a pool with
# no K/V layer apart), and the same after it.
PINNED = {
    "starcoder2-3b": {
        "serve_decode": "0d501d08e4e8b8f4c9f5f7ddd5a452c7"
                        "95d0daf517e63070f1caf40b5f879d0d",
        "serve_prefill": "c719994826e0241107446259f665cbfe"
                         "2381502cef14b703a47a482dedbe6f26",
    },
    "granite-4.0-h-small-l10e36": {
        "serve_decode": "bbdb7edb321c66497b9a3303eb4fb762"
                        "0efeac885d3e7810b1f7f6ddc1ccb57d",
        "serve_prefill": "ba035762d1de32256aec19839d7c127"
                         "06794911faafbfead8d3308639bc31ce6",
    },
    "keye-vl-2.0-30b-a3b-l8e16": {
        "serve_decode": "487c0e3b5e10a883e4629c7717b75733"
                        "608edd000122026658d1a5116c4895be",
        "serve_prefill": "680f4f929fe1b10a541749884649ff0b"
                         "96f548a170c25ee8ce2faccc4e2733c5",
    },
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_the_other_serving_programs_trace_as_before(config):
    import importlib

    from tpu_ddp.serve.kv_pool import index_page

    cfg = json.loads((ROOT / f"benchmark/configs/{config}.json").read_text())
    family = importlib.import_module(
        f"benchmark.lib.families.{cfg.get('family', 'gpt')}")
    geo = cfg["serve"]
    S, B, C = geo["num_slots"], geo["block_size"], geo["prefill_chunk"]
    bps = geo["max_seq_len"] // B
    m = family.build(cfg, max_seq_len=geo["max_seq_len"],
                     param_dtype=jnp.bfloat16)
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    p = jax.eval_shape(m.init, jax.random.key(0))
    L = sum(x != "mamba" for x in m.mixers)
    pool = sds((L, S * bps + 1, B, m.kv_heads * m.head_dim), jnp.bfloat16)
    more = {k: sds(v.shape, v.dtype) for k, v in m.state_shapes(S).items()}
    if getattr(m, "index_dim", 0):
        more["index"] = sds((L, S * bps + 1) + index_page(B, m.index_dim),
                            jnp.bfloat16)
    more = (more,) if more else ()        # the third donated argument
    slot = (sds((), i32),) if more else ()
    lowered = {
        "serve_decode": engine_mod._build_decode_step(m, B, bps).lower(
            p, pool, pool, *more, sds((S, bps), i32), sds((S,), i32),
            sds((S,), i32), sds((S,), f32), sds((S,), i32)),
        "serve_prefill": engine_mod._build_prefill_step(m, B, bps).lower(
            p, pool, pool, *more, sds((bps,), i32), sds((1, C), i32),
            sds((), i32), sds((), i32), sds((), f32), sds((), i32), *slot),
    }
    got = {name: hashlib.sha256(low.as_text().encode()).hexdigest()
           for name, low in lowered.items()}
    assert got == PINNED[config]
