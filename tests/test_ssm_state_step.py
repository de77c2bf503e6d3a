"""The one-token state kernel (tpu_ddp/ops/pallas/ssm_state_step.py) in
interpreter mode, against the plain body it replaces in the decode step
(tpu_ddp/models/hybrid.py ``advance_state``): the new state and ``y`` of
the advanced slots, every other layer of the pool and every slot that is
not active bit for bit with non-finite values in its riding row, the
predicate, and an engine on a model the predicate refuses against the
same engine with the kernel forced on.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models import hybrid
from tpu_ddp.ops.pallas import ssm_state_step as kernel
from tpu_ddp.serve import ServeEngine
from tpu_ddp.serve import engine as engine_mod

LAYERS, LAYER = 3, 1


def _inputs(slots, heads, head_dim, n, groups, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return dict(
        pool=jax.random.normal(ks[0], (LAYERS, slots, heads, head_dim, n)),
        decay=jax.random.uniform(ks[1], (slots, heads), minval=0.2,
                                 maxval=1.0),
        dtx=jax.random.normal(ks[2], (slots, heads, head_dim)) * 0.1,
        b=jax.random.normal(ks[3], (slots, groups, n)) * 0.3,
        c=jax.random.normal(ks[4], (slots, groups, n)) * 0.05)


def _run(pool, decay, dtx, b, c, active):
    """(y, pool) of the kernel, as numpy; the pool argument is donated,
    so it is handed over as a copy."""
    y, new = kernel.ssm_state_step(jnp.array(pool), decay, dtx, b, c,
                                   layer=LAYER,
                                   active=jnp.asarray(active))
    return np.asarray(y), np.asarray(new)


# slots, heads, head_dim, N, groups: what the predicate takes. The third
# has more heads a group than a tile holds (two tiles a group), the
# fourth two lane tiles of N.
SHAPES = [(3, 4, 8, 128, 1), (3, 4, 8, 128, 2), (2, 128, 64, 128, 2),
          (2, 6, 16, 256, 3)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_kernel_matches_the_plain_body(shape):
    slots, heads, head_dim, n, groups = shape
    if heads == 128:
        # 2 MB tiles of 64 heads: the group's 64 heads are one tile
        assert kernel._heads_per_tile(heads, groups, 4 * head_dim * n) == 64
    x = _inputs(*shape)
    old = np.asarray(x["pool"])
    y, new = _run(active=np.ones(slots, bool), **x)
    want_y, want = hybrid.advance_state(
        x["pool"][LAYER], x["decay"], x["dtx"], x["b"], x["c"])
    np.testing.assert_allclose(new[LAYER], want, atol=1e-6, rtol=0)
    np.testing.assert_allclose(y, want_y, atol=1e-6, rtol=1e-6)
    others = [i for i in range(LAYERS) if i != LAYER]
    assert np.array_equal(new[others], old[others])


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_a_slot_that_is_not_active_is_left_bit_for_bit(groups, poison):
    """Rule 1 of the state pool: the idle slot's riding row holds
    non-finite ``x`` and ``B`` (and decay), its state does not move and
    nothing non-finite reaches a neighbour."""
    shape = (3, 4, 8, 128, groups)
    x = _inputs(*shape, seed=1)
    idle = 1
    active = np.array([True, False, True])
    x["dtx"] = x["dtx"].at[idle].set(poison)
    x["b"] = x["b"].at[idle].set(poison)
    x["decay"] = x["decay"].at[idle].set(poison)
    old = np.asarray(x["pool"])
    y, new = _run(active=active, **x)
    assert np.array_equal(new[LAYER, idle], old[LAYER, idle])
    assert np.array_equal(new[[0, 2]], old[[0, 2]])
    assert np.all(y[idle] == 0.0)
    want_y, want = hybrid.advance_state(
        x["pool"][LAYER], x["decay"], x["dtx"], x["b"], x["c"])
    np.testing.assert_allclose(new[LAYER, active], want[active], atol=1e-6)
    np.testing.assert_allclose(y[active], want_y[active], atol=1e-6,
                               rtol=1e-6)
    assert np.all(np.isfinite(new)) and np.all(np.isfinite(y))


def test_the_layer_is_an_operand_not_a_constant():
    """Every layer through ONE traced kernel: the jitted wrapper holds
    one entry after all of them."""
    x = _inputs(2, 4, 8, 128, 1, seed=2)
    pool = jnp.array(x["pool"])
    before = kernel._impl._cache_size()
    for layer in range(LAYERS):
        _, pool = kernel.ssm_state_step(
            pool, x["decay"], x["dtx"], x["b"], x["c"], layer=layer,
            active=jnp.ones(2, bool))
    assert kernel._impl._cache_size() == before + 1
    want = hybrid.advance_state(x["pool"][2], x["decay"], x["dtx"], x["b"],
                                x["c"])[1]
    np.testing.assert_allclose(pool[2], want, atol=1e-6)


@pytest.mark.parametrize("head_dim,n,dtype,takes", [
    (64, 128, jnp.float32, True), (8, 256, jnp.float32, True),
    (8, 16, jnp.float32, False), (4, 128, jnp.float32, False),
    (64, 128, jnp.bfloat16, False), (64, 192, jnp.float32, False),
])
def test_predicate(head_dim, n, dtype, takes):
    assert kernel.supports(head_dim, n, dtype) is takes


def test_shapes_the_predicate_refuses_raise():
    x = _inputs(2, 4, 8, 16, 1)
    with pytest.raises(ValueError, match="does not take"):
        kernel.ssm_state_step(x["pool"], x["decay"], x["dtx"], x["b"],
                              x["c"], layer=0, active=jnp.ones(2, bool))
    x = _inputs(2, 4, 8, 128, 1)
    with pytest.raises(ValueError, match="decay"):
        kernel.ssm_state_step(x["pool"], x["decay"][:, :2], x["dtx"],
                              x["b"], x["c"], layer=0,
                              active=jnp.ones(2, bool))


# ---- through the engine ----------------------------------------------------------

GEOM = dict(num_slots=2, block_size=8, prefill_chunk=8)


def _model(**kw):
    cfg = dict(vocab_size=256, d_model=64, num_heads=4, num_kv_heads=2,
               d_ff=32, shared_ff=48, num_experts=8, top_k=3,
               ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_chunk=8,
               layer_types=("mamba", "attention", "mamba"),
               max_seq_len=64, compute_dtype=jnp.float32)
    cfg.update(kw)
    return hybrid.HybridLM(**cfg)


class _Bf16State(hybrid.HybridLM):
    """A model whose recurrent state is kept in bfloat16."""

    def state_shapes(self, num_slots):
        shapes = super().state_shapes(num_slots)
        return dict(shapes, ssm=jax.ShapeDtypeStruct(
            shapes["ssm"].shape, jnp.dtype(jnp.bfloat16)))


def _serve(model):
    engine_mod._build_decode_step.cache_clear()
    try:
        eng = ServeEngine(model, model.init(jax.random.key(4)), **GEOM)
        rng = np.random.default_rng(0)
        reqs = [eng.submit(rng.integers(0, 256, size=n), new)
                for n, new in ((11, 6), (5, 9), (19, 4))]
        eng.run()
        text = eng.lower_decode_step().as_text(debug_info=True)
    finally:
        engine_mod._build_decode_step.cache_clear()
    assert all(r.done for r in reqs)
    return reqs, text


def _has_kernel(text: str) -> bool:
    return '"ssm_state_step/pallas_call"' in text


@pytest.mark.parametrize("kw", [dict(ssm_state=16), dict(ssm_head_dim=4)],
                         ids=["N16", "head_dim4"])
def test_a_refused_model_takes_the_plain_body_and_gives_the_same_tokens(
        kw, monkeypatch):
    """The predicate refuses the shapes for Mosaic's tiling; the
    interpreter takes any. So the same model is served twice, by the
    plain body as the predicate says and by the kernel forced on, and
    the two give the same tokens and log-probabilities."""
    model = _model(**kw)
    assert not kernel.supports(model.ssm_head_dim, model.ssm_state,
                               jnp.float32)
    plain, text = _serve(model)
    assert not _has_kernel(text)
    assert re.search(r"jit\(serve_decode\)/ssm/state/scatter", text)
    monkeypatch.setattr(kernel, "supports", lambda *a: True)
    forced, text = _serve(model)
    assert _has_kernel(text)
    for p, f in zip(plain, forced):
        assert list(p.tokens) == list(f.tokens)
        np.testing.assert_allclose(p.logprobs, f.logprobs, atol=2e-5)


def test_a_state_that_is_not_float32_takes_the_plain_body():
    model = _Bf16State(**dataclasses.asdict(_model(ssm_state=128)))
    assert kernel.supports(model.ssm_head_dim, model.ssm_state, jnp.float32)
    reqs, text = _serve(model)
    assert not _has_kernel(text)
    assert all(np.all(np.isfinite(r.logprobs)) for r in reqs)
    # the same widths in float32 take the kernel, and agree with bfloat16
    # state as far as 8 bits of it go
    want, text = _serve(_model(ssm_state=128))
    assert _has_kernel(text)
    assert np.abs(np.asarray(want[0].logprobs[:2])
                  - np.asarray(reqs[0].logprobs[:2])).max() < 0.2
