"""A model with two kinds of state on the serving path (``HybridLM``:
Mamba-2 state beside paged K/V, a dropless expert layer that holds a
share of its experts) against the plain reference
(benchmark/reference/granite_hybrid.py), on the CPU at a small size with
seeded random weights and float32 compute: log-probabilities through
chunked prefill and decode, the chunked scan against the plain one, the
share test, the three rules of the state pool (each with a run that
breaks it), step-ahead with a cancel and a quarantine, and every serving
feature that refuses such a model.
"""

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

from benchmark.reference import granite_hybrid as reference  # noqa: E402
from tpu_ddp.models import decode, hybrid  # noqa: E402
from tpu_ddp.models.hybrid import HybridLM  # noqa: E402
from tpu_ddp.ops.pallas import ssm_state_step  # noqa: E402
from tpu_ddp.parallel.moe import dropless_moe  # noqa: E402
from tpu_ddp.serve import ServeEngine  # noqa: E402
from tpu_ddp.serve import engine as engine_mod  # noqa: E402

GEOM = dict(num_slots=3, block_size=8, prefill_chunk=8)
TOL = 2e-5      # float32 on both sides: only the order of sums differs


def _model(**kw):
    cfg = dict(vocab_size=256, d_model=64, num_heads=4, num_kv_heads=2,
               d_ff=32, shared_ff=48, num_experts=8, top_k=3, held=(4, 8),
               ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_chunk=8,
               layer_types=("mamba", "mamba", "attention", "mamba"),
               embedding_multiplier=12.0, residual_multiplier=0.22,
               attention_multiplier=0.0625, logits_scaling=4.0,
               max_seq_len=64, compute_dtype=jnp.float32)
    cfg.update(kw)
    return HybridLM(**cfg)


def _cfg(model) -> dict:
    """The reference's side of ``model``: the published keys it reads."""
    return {"rms_norm_eps": model.norm_eps,
            "num_experts_per_tok": model.top_k,
            "held_experts": list(model.held),
            "mamba_n_groups": model.ssm_groups,
            "attention_multiplier": model.attn_scale,
            "residual_multiplier": model.residual_multiplier,
            "embedding_multiplier": model.embedding_multiplier,
            "logits_scaling": model.logits_scaling}


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.key(7))


@pytest.fixture(scope="module", params=[16, 128], ids=["plain", "kernel"])
def served(request, model, params):
    """(model, params) by how the decode step advances the state: N 16,
    which the kernel's predicate refuses (the plain body), and N 128,
    which it takes (ops/pallas/ssm_state_step.py, interpreted here)."""
    if request.param == model.ssm_state:
        return model, params
    m = _model(ssm_state=request.param)
    assert ssm_state_step.supports(m.ssm_head_dim, m.ssm_state, jnp.float32)
    return m, m.init(jax.random.key(7))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, size=n)


def _worst(model, params, prompt, req) -> float:
    """Largest difference between the log-probabilities the engine
    reported for ``req`` and the reference's full forward pass."""
    full = np.concatenate([prompt, req.tokens]).astype(np.int32)
    ref = np.asarray(reference.log_probs(params, jnp.asarray(full[:-1]),
                                         _cfg(model)))
    at = np.arange(len(prompt) - 1, len(full) - 1)
    return float(np.max(np.abs(ref[at, full[at + 1]]
                               - np.asarray(req.logprobs))))


# Prompts of one to four chunks, with and without padding in the last;
# seven requests on three slots, so slots turn over and a long prompt is
# between two chunks while its neighbours decode.
CASES = [(19, 6), (5, 9), (8, 4), (30, 5), (3, 12), (16, 3), (25, 7)]


def _serve(model, params, cases=CASES, **kw):
    eng = ServeEngine(model, params, **dict(GEOM, **kw))
    prompts = [_prompt(n, seed=i) for i, (n, _) in enumerate(cases)]
    reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, cases)]
    eng.run()
    return eng, prompts, reqs


def test_engine_logprobs_match_the_reference(served):
    model, params = served
    eng, prompts, reqs = _serve(model, params)
    for p, r, (_, new) in zip(prompts, reqs, CASES):
        assert r.done and len(r.tokens) == new
        assert _worst(model, params, p, r) < TOL
    assert eng.pool.k.shape[0] == 1         # pages for one layer of four
    assert eng.state.arrays["ssm"].shape == (3, 3, 4, 8, model.ssm_state)
    assert eng.state.arrays["ssm"].dtype == jnp.float32
    assert eng.sched.accounting_ok()
    assert eng.pool.free_count == eng.pool.total_usable


def test_two_groups_and_a_chunk_that_is_not_the_scans(params):
    """B and C shared by half the heads each; a prefill chunk of 12
    over a scan chunk of 8 falls back to one piece."""
    m = _model(ssm_groups=2)
    p = m.init(jax.random.key(3))
    _, prompts, reqs = _serve(m, p, CASES[:4], prefill_chunk=12)
    for prompt, r in zip(prompts, reqs):
        assert _worst(m, p, prompt, r) < TOL


def test_bf16_serving_keeps_the_state_in_float32():
    m = _model(compute_dtype=jnp.bfloat16)
    eng = ServeEngine(m, m.init(jax.random.key(1)), **GEOM)
    assert eng.state.arrays["ssm"].dtype == jnp.float32
    assert eng.state.arrays["conv"].dtype == jnp.bfloat16
    req = eng.submit(_prompt(11, 0), 4)
    eng.run()
    assert req.done and all(map(np.isfinite, req.logprobs))


# ---- the scan -----------------------------------------------------------------

def test_chunked_form_and_single_steps_match_the_plain_scan(model, params):
    """One sequence of 29 positions through ``ssm_chunk`` in runs of 8
    (the last padded), and then token by token through ``ssm_step``,
    against the reference's ``lax.scan`` over positions."""
    blk = params["blocks"][0]
    L, C = 29, 8
    x = jax.random.normal(jax.random.key(2), (L, model.d_model))
    want = reference._mamba(
        {k: blk[k] for k in ("ln1", "in_proj", "conv_w", "conv_b",
                             "dt_bias", "A_log", "D", "norm", "out_proj")},
        x, eps=model.norm_eps, groups=1)
    h = model.norm(x, blk["ln1"])
    shapes = model.state_shapes(1)
    ssm = jnp.zeros(shapes["ssm"].shape[2:])
    conv = jnp.zeros(shapes["conv"].shape[2:])
    outs = []
    for i in range(0, 24, C):
        o, ssm, conv = hybrid.ssm_chunk(model, blk, h[i:i + C], ssm, conv,
                                        C)
        outs.append(o)
    padded = jnp.concatenate([h[24:], jnp.ones((3, model.d_model))])
    o, ssm_pad, conv_pad = hybrid.ssm_chunk(model, blk, padded, ssm, conv,
                                            5)
    outs.append(o[:5])
    np.testing.assert_allclose(jnp.concatenate(outs), want, atol=TOL)
    # the same five positions one at a time: same output, same state
    for t in range(24, L):
        o, ssm, conv = hybrid.ssm_step(model, blk, h[t][None], ssm[None],
                                       conv[None])
        ssm, conv = ssm[0], conv[0]
        np.testing.assert_allclose(o[0], want[t], atol=TOL)
    np.testing.assert_allclose(ssm_pad, ssm, atol=TOL)
    np.testing.assert_allclose(conv_pad, conv, atol=TOL)


# ---- the share ----------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(params):
    """The routed parts that the two halves of the experts give, plus
    the shared MLP once, are the uncut reference's layer."""
    whole = _model(held=(0, 8))
    p = whole.init(jax.random.key(5))
    blk = p["blocks"][1]
    x = jax.random.normal(jax.random.key(6), (13, whole.d_model)) * 3.0
    want = reference.moe(blk, x, _cfg(whole))
    h = whole.norm(x, blk["ln2"])
    parts = [dropless_moe(h, blk["router"], blk["w1"][lo:hi],
                          blk["w2"][lo:hi], top_k=whole.top_k,
                          held=(lo, hi)) for lo, hi in ((0, 4), (4, 8))]
    shared = decode.gated_mlp(whole, h, blk["shared_w1"], blk["shared_w2"])
    # each half computes something, and neither the whole
    assert all(float(jnp.abs(part).max()) > 1e-3 for part in parts)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=TOL, rtol=1e-5)
    # and the reference cut the same way gives its half
    half = dict(blk, w1=blk["w1"][4:], w2=blk["w2"][4:])
    np.testing.assert_allclose(
        parts[1] + shared,
        reference.moe(half, x, dict(_cfg(whole), held_experts=[4, 8])),
        atol=TOL, rtol=1e-5)
    # decode.mlp is the share plus the shared MLP
    cut = _model(held=(4, 8))
    np.testing.assert_allclose(decode.mlp(cut, half, h[None])[0],
                               parts[1] + shared, atol=TOL, rtol=1e-5)


def test_routing_shapes_are_static_whatever_the_routing(model, params):
    """Every token to absent experts, or every token to one held
    expert: the same program, a defined result."""
    blk = params["blocks"][0]
    x = jnp.ones((6, model.d_model))
    f = jax.jit(lambda r: dropless_moe(
        x, r, blk["w1"], blk["w2"], top_k=model.top_k, held=model.held))
    away = jnp.zeros((model.d_model, 8)).at[:, :3].set(1.0)
    assert float(jnp.abs(f(away)).max()) == 0.0
    here = jnp.zeros((model.d_model, 8)).at[:, 4:7].set(1.0)
    out = f(here)
    assert f._cache_size() == 1 and bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.abs(out).max()) > 0.0


# ---- the three rules of the state pool ----------------------------------------

def _break_untouched(monkeypatch):
    """Rule 1 off: the decode step advances every slot's state."""
    real = engine_mod.state_step
    monkeypatch.setattr(
        engine_mod, "state_step",
        lambda model, blk, x, state, si, active: real(
            model, blk, x, state, si, jnp.ones_like(active)))


def _break_fresh(monkeypatch):
    """Rule 2 off: a first chunk starts from what the slot holds."""
    real = engine_mod.state_chunk
    monkeypatch.setattr(
        engine_mod, "state_chunk",
        lambda model, blk, x, state, si, slot, fresh, n_valid: real(
            model, blk, x, state, si, slot, False, n_valid))


def _break_padding(monkeypatch):
    """Rule 3 off: the padding of a final chunk advances the state."""
    real = engine_mod.state_chunk
    monkeypatch.setattr(
        engine_mod, "state_chunk",
        lambda model, blk, x, state, si, slot, fresh, n_valid: real(
            model, blk, x, state, si, slot, fresh, x.shape[1]))


# rule -> (how to break it, the requests that show it, the one to look at)
RULES = {
    # a prompt of four chunks on slot 1 while slot 0 decodes
    "untouched_between_chunks": (_break_untouched, [(4, 12), (30, 4)], 1),
    # slot 0: a long tenant, then a shorter one
    "zero_at_the_first_chunk": (_break_fresh, [(27, 3), (6, 5)], 1),
    # 13 tokens in chunks of 8: three rows of padding
    "padding_does_not_advance": (_break_padding, [(13, 6)], 0),
}


@pytest.mark.parametrize("rule", sorted(RULES))
@pytest.mark.parametrize("broken", [False, True])
def test_each_state_rule_holds_and_is_needed(served, monkeypatch, rule,
                                             broken):
    model, params = served
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


# ---- step-ahead, cancel, quarantine -------------------------------------------

def test_step_ahead_with_a_cancel_and_a_quarantine(model, params):
    """The plain engine runs one decode step ahead of its readback. A
    request cancelled with a step in flight, and one whose state went
    non-finite and was quarantined, leave nothing that their slots'
    next tenants or their neighbours can read."""
    eng = ServeEngine(model, params, **GEOM)
    cases = [(9, 20), (12, 20), (5, 20), (17, 6), (7, 8), (21, 5)]
    prompts = [_prompt(n, seed=40 + i) for i, (n, _) in enumerate(cases)]
    reqs = [eng.submit(p, new) for p, (_, new) in zip(prompts, cases)]
    for _ in range(6):
        eng.step()
    assert eng._unread is not None           # a step is in flight
    assert eng.cancel(reqs[0])
    # poison request 1's state where it lives; the engine is at rest
    # after the cancel, as the chaos drill finds it
    slot = next(i for i, s in enumerate(eng.sched.slots)
                if s is not None and s.request is reqs[1])
    eng.state.arrays = {k: a.at[:, slot].set(jnp.nan)
                        for k, a in eng.state.arrays.items()}
    with pytest.warns(UserWarning, match="quarantin"):
        eng.run()
    assert reqs[0].cancelled and reqs[1].quarantined
    assert all(r.done for r in reqs)
    for p, r, (_, new) in list(zip(prompts, reqs, cases))[2:]:
        assert len(r.tokens) == new
        assert _worst(model, params, p, r) < TOL
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in eng.state.arrays.values())
    assert eng.sched.accounting_ok()
    assert eng.pool.free_count == eng.pool.total_usable


def test_step_ahead_gives_the_synchronous_engines_tokens(model, params):
    ahead, _, got = _serve(model, params, CASES[:5])
    sync = ServeEngine(model, params, **GEOM)
    want = [sync.submit(_prompt(n, seed=i), new)
            for i, (n, new) in enumerate(CASES[:5])]
    while sync.run(max_steps=1):
        pass
    assert ahead.metrics.counters.get("serve_decode_ahead", 0) > 0
    for g, w in zip(got, want):
        assert list(g.tokens) == list(w.tokens)
        assert g.logprobs == w.logprobs


# ---- what refuses --------------------------------------------------------------

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


def test_disagg_refuses_with_the_reason(model, params):
    from tpu_ddp.fleet.disagg import DisaggEngine
    with pytest.raises(ValueError, match=r"disagg \(the edge ships"):
        DisaggEngine(model, params, **GEOM)


def test_a_model_without_state_is_refused_nothing():
    from tpu_ddp.models.transformer import make_transformer
    decode.check_state_servable(
        make_transformer("TransformerLM-tiny"), prefix_cache=True,
        kv_tiers=True, spec_k=True, disagg=True)


# ---- the lowered programs ------------------------------------------------------

# The state kernel in a lowered program: its ``pallas_call`` under its
# ``name=``, inside the jitted wrapper that the program calls from
# ``ssm/state`` (the compiled program spells the path in one piece:
# tests/test_serve_prefill_tpu_compile.py).
KERNEL_CALL = '"ssm_state_step/pallas_call"'


def test_programs_slice_no_layer_out_of_a_pool_and_donate_the_state():
    """37 blocks, unlike every other dimension: a value shaped (37, ...)
    or (1, 37, ...) can only be one layer sliced out of the K/V pool
    (tests/test_serve.py holds the dense programs to the same). The two
    K/V pools and the two state arrays are donated."""
    m = _model(layer_types=("mamba", "attention", "mamba", "attention"))
    eng = ServeEngine(m, m.init(jax.random.key(0)), num_blocks=37, **GEOM)
    layer = re.compile(r"tensor<(?:1x)?37x[^>]*>")
    for lower, name in ((eng.lower_decode_step, "serve_decode"),
                        (eng.lower_prefill_step, "serve_prefill")):
        text = lower().as_text()
        assert f"module @jit_{name}" in text
        assert not layer.findall(text), name
        main = next(ln for ln in text.splitlines()
                    if "func.func public @main" in ln).split(") -> ")[0]
        donated = re.findall(
            r"tensor<2x(?:37|3)x[^>]*> \{[^%]*(?:tf\.aliasing_output|"
            r"jax\.buffer_donor)", main)
        assert len(donated) == 4, (name, main[:600])
    text = eng.lower_decode_step().as_text(debug_info=True)
    for scope in ("ssm/state", "mlp/moe/route", "mlp/moe/experts",
                  "mlp/shared_mlp", "attn/kv_write"):
        assert f"jit(serve_decode)/{scope}/" in text, scope
    assert KERNEL_CALL not in text      # N 16: the plain body


def test_decode_names_the_state_kernel_under_ssm_state_and_prefill_does_not():
    m = _model(ssm_state=128,
               layer_types=("mamba", "attention", "mamba", "attention"))
    eng = ServeEngine(m, m.init(jax.random.key(0)), num_blocks=37, **GEOM)
    decode = eng.lower_decode_step().as_text(debug_info=True)
    assert KERNEL_CALL in decode
    assert "jit(serve_decode)/ssm/state/jit(_impl)" in decode
    # the kernel is called once a state layer, on the whole pool; no
    # layer of the recurrence's state is sliced out or scattered back
    main = decode[decode.index("func.func public @main"):]
    main = main[:main.index("\n  }")]
    calls = re.findall(r"call @_impl[^\n]*", main)
    assert len(calls) == 2 and all("tensor<2x3x4x8x128xf32>" in c
                                   for c in calls)
    assert not re.findall(r"tensor<(?:1x)?3x4x8x128xf32>", main)
    prefill = eng.lower_prefill_step().as_text(debug_info=True)
    assert "jit(serve_prefill)/ssm/state/" in prefill
    assert KERNEL_CALL not in prefill and "@_impl" not in prefill


# ---- the grouped products through the kernel (PR 37) --------------------------

# bf16 compute at widths the grouped-matmul predicate takes (d_model and
# d_ff whole lane tiles), on a geometry whose sorted rows are whole
# packed registers: 4 slots x top-4 = 16 a decode step, 8 x 4 = 32 a chunk.
KERNEL_GEOM = dict(num_slots=4, block_size=8, prefill_chunk=8)
GROUPED_CALL = '"grouped_matmul/pallas_call"'
BF16_TOL = 0.06     # bf16 through four layers against the float32 reference


def _kernel_model(**kw):
    return _model(d_model=128, d_ff=128, shared_ff=64, top_k=4,
                  compute_dtype=jnp.bfloat16, **kw)


def test_dropless_moe_through_the_kernel_matches_the_per_expert_reference():
    """The expert layer alone, bf16, both products through the kernel,
    against the reference's loop over experts in float32 on the same
    (bf16-rounded) weights."""
    from tpu_ddp.ops.pallas import grouped_matmul
    m = _kernel_model()
    blk = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                       m.init(jax.random.key(5))["blocks"][1])
    x = jax.random.normal(jax.random.key(6), (12, m.d_model)) * 3.0
    h = m.norm(x, blk["ln2"]).astype(jnp.bfloat16)
    assert grouped_matmul.supports(12 * m.top_k, m.d_model, 2 * m.d_ff,
                                   h.dtype, jnp.bfloat16)
    f = lambda h: dropless_moe(  # noqa: E731
        h, blk["router"], blk["w1"].astype(jnp.bfloat16),
        blk["w2"].astype(jnp.bfloat16), top_k=m.top_k, held=m.held)
    assert str(jax.make_jaxpr(f)(h)).count("name=grouped_matmul") == 2
    shared = decode.gated_mlp(m, h.astype(jnp.float32), blk["shared_w1"],
                              blk["shared_w2"])
    want = reference.moe(blk, x, _cfg(m)) - shared
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(f(h), want, atol=0.03, rtol=0.03)


@pytest.mark.parametrize("body", ["kernel", "plain"])
def test_engine_logprobs_match_the_reference_in_bf16(monkeypatch, body):
    """The engine's two programs with the grouped products through the
    kernel (interpreted here), and the same engine held to
    ``lax.ragged_dot``: each within bf16's distance of the float32
    reference, each program naming what it runs under ``moe/experts``."""
    from tpu_ddp.ops.pallas import grouped_matmul
    if body == "plain":
        monkeypatch.setattr(grouped_matmul, "supports", lambda *a: False)
    # the engine's step builders are cached by the model: a name apart
    m = _kernel_model(name=f"hybrid-{body}")
    p = m.init(jax.random.key(7))
    eng, prompts, reqs = _serve(m, p, CASES[:5], **KERNEL_GEOM)
    for prompt, r, (_, new) in zip(prompts, reqs, CASES):
        assert r.done and len(r.tokens) == new
        assert _worst(m, p, prompt, r) < BF16_TOL
    for lower, name in ((eng.lower_decode_step, "serve_decode"),
                        (eng.lower_prefill_step, "serve_prefill")):
        text = lower().as_text(debug_info=True)
        assert f"jit({name})/mlp/moe/experts/" in text, name
        assert (GROUPED_CALL in text) == (body == "kernel"), name
        # the primitive's name in the op paths: a test that traced the
        # kernel first leaves its own name (``..._ragged_dot``) in the
        # cached kernel body's locations
        assert ("ragged_dot_general" in text) == (body == "plain"), name
