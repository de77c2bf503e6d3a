"""The sparse prefill kernel (ops/pallas/sparse_prefill_attention.py),
interpreted on the CPU, against ``decode.attend_masked``: masks made by
``sparse_moe.selection_mask`` from random scores at query positions
spread over the table, a row that keeps one position, rows whose kept
positions lie in one key block, a mask that keeps every position up to
the query, several query and key blocks, and the shapes ``supports``
refuses (where ``serve/engine.py`` ``sparse_chunk`` keeps the jnp form).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_ddp.models import decode, sparse_moe
from tpu_ddp.ops.pallas import sparse_prefill_attention as spa

HD = 128


def _qkv(C, T, H, KV, dtype, seed):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (C, H, HD), jnp.float32).astype(dtype)
    ck = jax.random.normal(kk, (T, KV, HD), jnp.float32).astype(dtype)
    cv = jax.random.normal(kv, (T, KV, HD), jnp.float32).astype(dtype)
    return q, ck, cv


def _selection(C, T, start, topk, seed):
    """What the indexer keeps for queries at ``start .. start + C - 1``
    from random scores: (C, T) bool."""
    model = types.SimpleNamespace(index_topk=topk)
    scores = jax.random.uniform(jax.random.key(seed + 1), (1, C, T))
    p = (start + jnp.arange(C))[None]
    return sparse_moe.selection_mask(model, scores, p)[0]


def _kernel(q, ck, cv, keep):
    return spa.sparse_prefill_attention(q, ck, cv, keep,
                                        scale=1.0 / HD ** 0.5)


def _plain(q, ck, cv, keep):
    model = types.SimpleNamespace(head_dim=HD)
    return decode.attend_masked(model, q[None], ck[None], cv[None],
                                keep[None])[0]


def _close(got, want):
    """float32: only the order of sums differs. bf16: the weights are
    rounded to bf16 for the weighted sum and the output is bf16, so two
    units of its last place at the output's largest value."""
    if got.dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        return
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    tol = 2 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(got - want).max() <= tol, (np.abs(got - want).max(), tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("C,T", [(32, 384), (64, 1536), (256, 2048)])
def test_the_kernel_attends_what_the_mask_keeps(C, T, dtype):
    """Query positions at the table's start, middle and end; 8 query
    heads a K/V head; one or several query and key blocks."""
    H, KV = 16, 2
    bq, bk = spa._tiles(C, T, H // KV)
    assert spa.supports(C, T, H, KV, HD, dtype)
    assert C % bq == 0 and T % bk == 0
    q, ck, cv = _qkv(C, T, H, KV, dtype, seed=C + T)
    for start in (0, (T - C) // 2, T - C):
        keep = _selection(C, T, start, topk=96, seed=start)
        assert bool(keep.any(axis=-1).all())    # the divide is safe
        got = _kernel(q, ck, cv, keep)
        assert got.shape == q.shape and got.dtype == q.dtype
        _close(got, _plain(q, ck, cv, keep))


def test_the_blocks_the_tiles_come_to():
    assert spa._tiles(512, 36864, 8) == (128, 1024)  # the keye cell
    assert spa._tiles(64, 1536, 8) == (64, 768)
    assert spa._tiles(256, 2048, 8) == (128, 1024)
    assert spa._tiles(32, 384, 8) == (32, 384)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_that_keeps_one_position_reads_its_value(dtype):
    C, T, H, KV = 32, 2048, 16, 2
    q, ck, cv = _qkv(C, T, H, KV, dtype, seed=3)
    keep = _selection(C, T, T - C, topk=64, seed=3)
    keep = keep.at[5].set(False).at[5, 0].set(True)      # position 0 only
    keep = keep.at[9].set(False).at[9, 1500].set(True)   # last block only
    got = np.asarray(_kernel(q, ck, cv, keep), np.float32)
    g = H // KV
    for row, pos in ((5, 0), (9, 1500)):
        want = np.repeat(np.asarray(cv[pos], np.float32), g, axis=0)
        np.testing.assert_array_equal(got[row], want)


def test_rows_kept_in_one_key_block_and_masked_blocks_beside_them():
    """A row whose kept positions all lie in the first or the last key
    block; whole key blocks masked for it, before or after. Those blocks
    leave the row as it was: the output is, to the bit, that of the
    kept block alone."""
    C, T, H, KV = 64, 3072, 16, 2
    q, ck, cv = _qkv(C, T, H, KV, jnp.float32, seed=4)
    assert spa._tiles(C, T, H // KV)[1] == 1024
    rng = np.random.default_rng(4)
    keep = np.zeros((C, T), bool)
    first = np.arange(C) < C // 2
    for i in range(C):
        lo = 0 if first[i] else 2048
        keep[i, lo + rng.choice(1024, size=40, replace=False)] = True
    keep = jnp.asarray(keep)
    got = _kernel(q, ck, cv, keep)
    _close(got, _plain(q, ck, cv, keep))
    got = np.asarray(got)
    for rows, lo in ((first, 0), (~first, 2048)):
        alone = np.asarray(_kernel(q[rows], ck[lo:lo + 1024],
                                   cv[lo:lo + 1024],
                                   keep[rows][:, lo:lo + 1024]))
        np.testing.assert_array_equal(got[rows], alone)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_mask_that_keeps_every_position_up_to_the_query(dtype):
    """Within the top-k the selection is causal attention: every
    position up to the query's own."""
    C, T, H, KV = 64, 1024, 16, 2
    q, ck, cv = _qkv(C, T, H, KV, dtype, seed=5)
    keep = _selection(C, T, 200, topk=T, seed=5)
    p = 200 + np.arange(C)
    np.testing.assert_array_equal(
        np.asarray(keep), np.arange(T)[None] <= p[:, None])
    _close(_kernel(q, ck, cv, keep), _plain(q, ck, cv, keep))


@pytest.mark.parametrize("C,T,H,KV,hd,dtype", [
    (32, 1024, 16, 2, 64, jnp.bfloat16),        # a head of 64 lanes
    (32, 1024, 16, 2, 128, jnp.float16),        # a dtype it does not load
    (48, 1024, 16, 2, 128, jnp.bfloat16),       # no whole query block
    (16, 1024, 16, 2, 128, jnp.bfloat16),
    (32, 1000, 16, 2, 128, jnp.bfloat16),       # no whole key block
    (32, 1024, 16, 3, 128, jnp.bfloat16),       # heads not grouped
    (32, 1024, 64, 1, 1024, jnp.bfloat16),      # beyond the VMEM budget
])
def test_supports_refuses_what_it_cannot_take(C, T, H, KV, hd, dtype):
    assert not spa.supports(C, T, H, KV, hd, dtype)
    q = jnp.zeros((C, H, hd), dtype)
    kv = jnp.zeros((T, KV, hd), dtype)
    with pytest.raises(ValueError, match="does not take"):
        spa.sparse_prefill_attention(q, kv, kv, jnp.ones((C, T), bool),
                                     scale=1.0)


def test_mismatched_operands_are_refused():
    q = jnp.zeros((32, 16, HD), jnp.bfloat16)
    kv = jnp.zeros((1024, 2, HD), jnp.float32)
    with pytest.raises(ValueError, match="q's dtype"):
        spa.sparse_prefill_attention(q, kv, kv, jnp.ones((32, 1024), bool),
                                     scale=1.0)
