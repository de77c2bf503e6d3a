"""The paged decode attention kernel (tpu_ddp/ops/pallas/
paged_attention.py), in interpreter mode on shapes inside its predicate:
against ``decode.attend_cached`` over the gathered view, which is what
the serve step ran before the kernel and still runs outside the
predicate. Pages past a slot's length, the tail of its last page and
other slots' pages must not reach its output, finite or not.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models.decode import attend_cached
from tpu_ddp.models.transformer import make_transformer
from tpu_ddp.ops.pallas import paged_attention
from tpu_ddp.ops.pallas.paged_attention import paged_decode_attention
from tpu_ddp.serve.engine import ServeEngine

L, KV, HD, BS, BPS = 2, 2, 128, 16, 12
FULL = BPS * BS
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _pools(dtype, slots, seed=0):
    """Random K/V pools and a block table that gives every slot its own
    pages, scattered over the pool (block 0 stays the null block)."""
    rng = np.random.default_rng(seed)
    n = slots * BPS + 1
    shape = (L, n, BS, KV * HD)
    pk = jnp.asarray(rng.standard_normal(shape), dtype)
    pv = jnp.asarray(rng.standard_normal(shape), dtype)
    tables = rng.permutation(np.arange(1, n)).reshape(slots, BPS)
    return pk, pv, jnp.asarray(tables, jnp.int32)


def _q(dtype, slots, group, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((slots, KV * group, HD)), dtype)


def _gathered(q, pk, pv, tables, lengths, layer):
    """The gather body: attend_cached over the layer's gathered view,
    the query at position ``length - 1``."""
    view = (q.shape[0], FULL, KV, HD)
    ck = pk[layer][tables].reshape(view)
    cv = pv[layer][tables].reshape(view)
    pos = (jnp.asarray(lengths, jnp.int32) - 1)[:, None]
    return attend_cached(SimpleNamespace(head_dim=HD), q[:, None], ck, cv,
                         pos)[:, 0]


def _paged(q, pk, pv, tables, lengths, layer=1):
    return paged_decode_attention(q, pk, pv, tables,
                                  jnp.asarray(lengths, jnp.int32),
                                  layer=layer, kv_heads=KV)


def _close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [12, 1], ids=["g12", "g1"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, FULL])
def test_matches_attend_cached_at_one_length(dtype, group, length):
    pk, pv, tables = _pools(dtype, slots=2)
    q = _q(dtype, 2, group)
    lengths = [length, length]
    got = _paged(q, pk, pv, tables, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    _close(got, _gathered(q, pk, pv, tables, lengths, 1), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [12, 1], ids=["g12", "g1"])
def test_slots_of_very_different_lengths_in_one_call(dtype, group):
    lengths = [FULL, 1, 129, 16, 3, 130]
    pk, pv, tables = _pools(dtype, slots=len(lengths))
    q = _q(dtype, len(lengths), group)
    _close(_paged(q, pk, pv, tables, lengths),
           _gathered(q, pk, pv, tables, lengths, 1), dtype)


@pytest.mark.parametrize("cache,compute", [(jnp.bfloat16, jnp.float32),
                                           (jnp.float32, jnp.bfloat16)],
                         ids=["bf16pool_f32q", "f32pool_bf16q"])
def test_pool_dtype_other_than_the_querys_is_cast_after_the_load(
        cache, compute):
    lengths = [40, 7]
    pk, pv, tables = _pools(cache, slots=2)
    q = _q(compute, 2, 12)
    got = _paged(q, pk, pv, tables, lengths)
    assert got.dtype == q.dtype
    _close(got, _gathered(q, pk, pv, tables, lengths, 1), jnp.bfloat16)


def test_null_table_with_length_zero_returns_zeros_beside_a_live_slot():
    pk, pv, tables = _pools(jnp.float32, slots=2)
    tables = tables.at[0].set(0)
    q = _q(jnp.float32, 2, 12)
    got = _paged(q, pk, pv, tables, [0, 20])
    assert np.all(np.asarray(got[0]) == 0)
    _close(got[1], _gathered(q, pk, pv, tables, [1, 20], 1)[1],
           jnp.float32)


@pytest.mark.parametrize("where", ["past_length", "last_page_tail",
                                   "other_slot", "null_block"])
@pytest.mark.parametrize("value", [np.nan, np.inf], ids=["nan", "inf"])
def test_nonfinite_values_a_slot_does_not_attend_change_nothing(where,
                                                                value):
    lengths = [37, 150]
    pk, pv, tables = _pools(jnp.float32, slots=2)
    q = _q(jnp.float32, 2, 12)
    clean = np.asarray(_paged(q, pk, pv, tables, lengths))
    t = np.asarray(tables)
    if where == "past_length":          # slot 0's pages 3.. are not live
        blocks, rows = t[0, 3:], slice(None)
    elif where == "last_page_tail":     # 37 = 2 pages + 5 positions
        blocks, rows = t[0, 2:3], slice(5, None)
    elif where == "other_slot":
        blocks, rows = t[1], slice(None)
    else:
        blocks, rows = np.array([0]), slice(None)
    idx = jnp.asarray(blocks)
    pk = pk.at[:, idx, rows].set(value)
    pv = pv.at[:, idx, rows].set(value)
    got = np.asarray(_paged(q, pk, pv, tables, lengths))
    np.testing.assert_array_equal(got[0], clean[0])
    if where != "other_slot":
        np.testing.assert_array_equal(got[1], clean[1])
    else:
        assert not np.all(np.isfinite(got[1]))


@pytest.mark.parametrize("layer", [0, 1])
def test_layer_index_selects_the_layer(layer):
    lengths = [33, 90]
    pk, pv, tables = _pools(jnp.float32, slots=2)
    q = _q(jnp.float32, 2, 12)
    got = _paged(q, pk, pv, tables, lengths, layer=layer)
    _close(got, _gathered(q, pk, pv, tables, lengths, layer), jnp.float32)
    other = _gathered(q, pk, pv, tables, lengths, 1 - layer)
    assert float(jnp.max(jnp.abs(got - other))) > 1e-2


@pytest.mark.parametrize("head_dim,block,pool,q,ok", [
    (128, 16, jnp.bfloat16, jnp.bfloat16, True),
    (128, 16, jnp.float32, jnp.float32, True),
    (128, 8, jnp.float32, jnp.float32, True),
    (256, 32, jnp.bfloat16, jnp.float32, True),
    (64, 16, jnp.bfloat16, jnp.bfloat16, False),    # half a lane tile
    (32, 16, jnp.float32, jnp.float32, False),
    (128, 8, jnp.bfloat16, jnp.bfloat16, False),    # half a bf16 tile
    (128, 12, jnp.float32, jnp.float32, False),
    (128, 32, jnp.int8, jnp.bfloat16, False),       # not a dtype it loads
    (128, 16, jnp.bfloat16, jnp.float16, False),
])
def test_predicate(head_dim, block, pool, q, ok):
    assert paged_attention.supports(head_dim, block, pool, q) is ok


def test_shapes_outside_the_predicate_are_refused_by_name():
    pk = jnp.zeros((1, 3, 16, 2 * 64), jnp.float32)
    with pytest.raises(ValueError, match="head_dim=64"):
        paged_decode_attention(
            jnp.zeros((1, 4, 64)), pk, pk, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), layer=0, kv_heads=2)
    with pytest.raises(ValueError, match="pools must both be"):
        paged_decode_attention(
            jnp.zeros((1, 4, 128)), pk, pk, jnp.zeros((1, 2), jnp.int32),
            jnp.ones((1,), jnp.int32), layer=0, kv_heads=2)


def _decode_text(model, block_size):
    eng = ServeEngine(model, model.init(jax.random.key(0)), num_slots=2,
                      block_size=block_size, prefill_chunk=8)
    return eng.lower_decode_step().as_text(debug_info=True)


def test_head_dim_64_engine_still_builds_the_gather_body():
    """The predicate refuses head_dim 64, and an engine on such a model
    lowers today's body: the gather scope and no kernel. A conforming
    model lowers the kernel and no gather."""
    small = make_transformer("TransformerLM-tiny", num_heads=2,
                             max_seq_len=64, compute_dtype=jnp.float32)
    assert small.head_dim == 64
    assert not paged_attention.supports(small.head_dim, 16, jnp.float32,
                                        jnp.float32)
    text = _decode_text(small, 16)
    assert "attn/kv_gather/" in text and "paged_decode_attn" not in text
    wide = make_transformer("TransformerLM-tiny", num_heads=2,
                            num_kv_heads=1, d_model=256, max_seq_len=64,
                            compute_dtype=jnp.float32)
    assert wide.head_dim == 128
    text = _decode_text(wide, 16)
    assert "paged_decode_attn" in text and "attn/kv_gather/" not in text
    assert "attn/kv_write/" in text
