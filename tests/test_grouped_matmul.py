"""The grouped-matmul kernel (tpu_ddp/ops/pallas/grouped_matmul.py) in
interpreter mode, against ``lax.ragged_dot``, the body it replaces in
the dropless expert layer (tpu_ddp/parallel/moe.py ``dropless_moe``):
empty groups, groups that start inside a packed register, a group of
more rows than a trip takes, every row in one group, rows past the last
group (zeros, whatever the rows hold), both aspect ratios and several
column tiles; the predicate's table; a refused shape raising from the
kernel and taking the plain body through ``dropless_moe``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpu_ddp.ops.pallas import grouped_matmul as kernel
from tpu_ddp.parallel import moe

BF16, F32 = jnp.bfloat16, jnp.float32


def _operands(m, k, n, groups, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    lhs = jax.random.normal(ks[0], (m, k), F32).astype(BF16)
    rhs = (jax.random.normal(ks[1], (groups, k, n), F32)
           * k ** -0.5).astype(BF16)
    return lhs, rhs


def _check(lhs, rhs, sizes):
    sizes = jnp.asarray(sizes, jnp.int32)
    out = kernel.grouped_matmul(lhs, rhs, sizes)
    assert out.shape == (lhs.shape[0], rhs.shape[2]) and out.dtype == F32
    want = lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=F32)
    total = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(out)[:total],
                               np.asarray(want)[:total],
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(out)[total:].any()
    return np.asarray(out)


# m, k, n, group sizes. A trip takes min(128, m) rows from the group's
# first row rounded down to 16.
CASES = {
    "empty_groups_between": (64, 128, 128, [0, 40, 0, 0, 24]),
    "every_group_empty": (32, 128, 128, [0, 0, 0]),
    "starts_inside_a_register": (64, 128, 128, [3, 17, 1, 30, 13]),
    "straddles_a_row_tile": (256, 128, 128, [100, 60, 96]),
    "more_rows_than_a_trip": (384, 128, 128, [10, 300, 74]),
    "every_row_in_one_group": (256, 128, 128, [0, 256, 0]),
    "rows_past_the_last_group": (256, 128, 128, [7, 20, 9]),
    "last_window_pulled_back": (144, 128, 128, [130, 14]),
    "fewer_rows_than_a_trip": (48, 128, 256, [20, 5, 23]),
    "one_row_a_group": (16, 128, 128, [1] * 16),
    "deep_and_narrow": (64, 512, 128, [30, 0, 34]),
    "shallow_and_wide": (64, 128, 640, [9, 41, 2]),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_kernel_matches_ragged_dot(case):
    m, k, n, sizes = CASES[case]
    _check(*_operands(m, k, n, len(sizes)), sizes)


@pytest.fixture
def narrow_tiles(monkeypatch):
    """A weight tile of 128 columns at k = 128, so that small products
    take several column tiles (the jitted body is cached by shape, not
    by the tile)."""
    monkeypatch.setattr(kernel, "_TILE_BYTES", 2 * 128 * 128)
    kernel._impl.clear_cache()
    yield
    kernel._impl.clear_cache()


@pytest.mark.parametrize("n", [256, 384])
def test_several_column_tiles(narrow_tiles, n):
    """The output block is zeroed at the first group of EVERY column
    tile, and each tile takes its own columns of each expert."""
    assert kernel._column_tile(128, n) == 128
    _check(*_operands(160, 128, n, 4, seed=3), [33, 0, 90, 20])


def test_column_tile_is_the_widest_that_divides_and_fits():
    # the benchmark's two products: 2 MB tiles
    assert kernel._column_tile(4096, 1536) == 256
    assert kernel._column_tile(768, 4096) == 1024
    assert kernel._column_tile(128, 384) == 384
    assert kernel._column_tile(16384, 128) == 0      # 4 MB for 128 columns


@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_rows_past_the_last_group_are_zeros_whatever_they_hold(poison):
    """Those rows are the assignments to absent experts: they share a
    trip's window with the last group's rows, and nothing of them may
    reach the output, not even as 0 * inf."""
    lhs, rhs = _operands(64, 128, 128, 3, seed=1)
    lhs = lhs.at[37:].set(poison)
    out = _check(lhs, rhs, [20, 0, 17])
    assert np.isfinite(out).all() and np.abs(out[:37]).max() > 0.1


def test_sizes_are_an_operand_not_a_constant():
    lhs, rhs = _operands(64, 128, 128, 3, seed=2)
    f = jax.jit(kernel.grouped_matmul)
    for sizes in ([64, 0, 0], [1, 2, 3], [0, 0, 0], [20, 20, 24]):
        out = f(lhs, rhs, jnp.asarray(sizes, jnp.int32))
        want = lax.ragged_dot(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                              preferred_element_type=F32)
        t = sum(sizes)
        np.testing.assert_allclose(out[:t], want[:t], rtol=1e-5, atol=1e-5)
        assert not np.asarray(out[t:]).any()
    assert f._cache_size() == 1


# m, k, n, lhs dtype, rhs dtype -> taken?
PREDICATE = [
    ((640, 4096, 1536, BF16, BF16), True),     # the cell's decode step
    ((640, 768, 4096, BF16, BF16), True),
    ((2560, 4096, 1536, BF16, BF16), True),    # the cell's prefill chunk
    ((2560, 768, 4096, BF16, BF16), True),
    ((16, 128, 128, BF16, BF16), True),
    ((640, 4096, 1536, F32, F32), False),      # the float32 reference's
    ((640, 4096, 1536, BF16, F32), False),
    ((640, 4096, 1536, F32, BF16), False),
    ((24, 128, 128, BF16, BF16), False),       # rows: half a register
    ((0, 128, 128, BF16, BF16), False),
    ((64, 64, 128, BF16, BF16), False),        # k, n: part of a lane tile
    ((64, 128, 96, BF16, BF16), False),
    ((64, 16384, 128, BF16, BF16), False),     # no 128 columns in a tile
    ((16384, 4096, 1536, BF16, BF16), False),  # the rows do not fit on chip
]


@pytest.mark.parametrize("args,taken", PREDICATE,
                         ids=[f"{a[0]}x{a[1]}x{a[2]}-{jnp.dtype(a[3]).name}-"
                              f"{jnp.dtype(a[4]).name}" for a, _ in PREDICATE])
def test_predicate(args, taken):
    assert kernel.supports(*args) is taken


@pytest.mark.parametrize("shape,dtype", [((24, 128, 128), BF16),
                                         ((32, 64, 128), BF16),
                                         ((32, 128, 128), F32)],
                         ids=["rows", "k", "float32"])
def test_a_refused_shape_raises_from_the_kernel(shape, dtype):
    m, k, n = shape
    lhs, rhs = jnp.zeros((m, k), dtype), jnp.zeros((2, k, n), dtype)
    with pytest.raises(ValueError, match="supports"):
        kernel.grouped_matmul(lhs, rhs, jnp.zeros((2,), jnp.int32))


def test_mismatched_operands_raise():
    lhs, rhs = _operands(32, 128, 128, 2)
    with pytest.raises(ValueError, match="group_sizes"):
        kernel.grouped_matmul(lhs, rhs, jnp.zeros((3,), jnp.int32))
    with pytest.raises(ValueError, match="lhs"):
        kernel.grouped_matmul(lhs[:, :64], rhs, jnp.zeros((2,), jnp.int32))


def _layer(dm, ff, dtype, tokens=8, experts=8, seed=4):
    ks = jax.random.split(jax.random.key(seed), 4)
    return dict(
        x=jax.random.normal(ks[0], (tokens, dm), F32).astype(dtype),
        router_w=jax.random.normal(ks[1], (dm, experts), F32).astype(dtype),
        w1=(jax.random.normal(ks[2], (4, dm, 2 * ff), F32)
            * dm ** -0.5).astype(dtype),
        w2=(jax.random.normal(ks[3], (4, ff, dm), F32)
            * ff ** -0.5).astype(dtype))


@pytest.mark.parametrize("dm,ff,dtype,kernels", [
    (128, 128, BF16, 2),        # both products through the kernel
    (128, 64, BF16, 1),         # the second contracts over 64: plain
    (64, 32, BF16, 0),
    (128, 128, F32, 0),
], ids=["both", "first_only", "neither", "float32"])
def test_dropless_moe_takes_the_kernel_where_the_predicate_does(
        dm, ff, dtype, kernels):
    """By the program it traces to: ``pallas_call``s named
    ``grouped_matmul`` for the products the predicate takes,
    ``ragged_dot`` for the rest."""
    layer = _layer(dm, ff, dtype)
    f = lambda **kw: moe.dropless_moe(  # noqa: E731
        kw["x"], kw["router_w"], kw["w1"], kw["w2"], top_k=4, held=(2, 6))
    text = str(jax.make_jaxpr(lambda kw: f(**kw))(layer))
    assert text.count("name=grouped_matmul") == kernels
    assert text.count("= ragged_dot_general[") == 2 - kernels


def test_dropless_moe_through_the_kernel_matches_the_plain_body(monkeypatch):
    layer = _layer(128, 128, BF16, tokens=12, seed=5)
    args = (layer["x"], layer["router_w"], layer["w1"], layer["w2"])
    got = moe.dropless_moe(*args, top_k=4, held=(2, 6))
    monkeypatch.setattr(kernel, "supports", lambda *a: False)
    want = moe.dropless_moe(*args, top_k=4, held=(2, 6))
    assert float(jnp.abs(want).max()) > 0.1
    # the same bf16 products summed in another order; SiLU(u) * v is
    # rounded to bf16 between the two, so a last bit can differ
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
