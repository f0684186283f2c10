"""ops/grouped_matmul.py, interpreted on the CPU, against one plain
product a group: the forward, the input gradient (``transpose_rhs``)
and the weight gradient (``moe_tgmm``), with groups that are empty,
that end inside a row tile, that span several tiles, and with a row
count that is no multiple of the tile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import grouped_matmul

SIZES = [
    [5, 0, 30, 13, 0, 16],   # empty groups, boundaries inside tiles
    [64, 0, 0, 0],           # everything in the first group
    [0, 0, 3, 45],           # leading empties, 48 rows
    [16, 16, 16, 16],        # boundaries on the tiles' edges
    [7, 11, 2],              # 20 rows: padded to the tile
]


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(grouped_matmul, "ROW_TILE", 16)


def _case(sizes, k=24, n=40):
    rng = np.random.default_rng(sum(sizes))
    m, g = sum(sizes), len(sizes)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(g, k, n)), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _loop(lhs, rhs, sizes):
    out, start = [], 0
    for g, size in enumerate(sizes):
        out.append(lhs[start:start + size] @ rhs[g])
        start += size
    return jnp.concatenate(out)


@pytest.mark.parametrize("sizes", SIZES)
def test_gmm_forward_matches_a_product_a_group(sizes):
    lhs, rhs, gs = _case(sizes)
    np.testing.assert_allclose(
        grouped_matmul.gmm(lhs, rhs, gs), _loop(lhs, rhs, sizes),
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("sizes", SIZES)
def test_gmm_gradients_match_a_product_a_group(sizes):
    lhs, rhs, gs = _case(sizes)
    weight = jnp.arange(lhs.shape[0] * 40, dtype=jnp.float32).reshape(-1, 40) / 500.0

    def loss(fn):
        return lambda l, r: jnp.sum(fn(l, r) * weight)

    got = jax.jit(jax.grad(loss(lambda l, r: grouped_matmul.gmm(l, r, gs)), (0, 1)))(lhs, rhs)
    want = jax.grad(loss(lambda l, r: _loop(l, r, sizes)), (0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-5)
    # An empty group's weight gradient is written, as zeros.
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.any(np.asarray(got[1][g]))


def test_bf16_in_bf16_out_float32_accumulation():
    lhs, rhs, gs = _case([40, 24], k=128, n=128)
    got = grouped_matmul.gmm(lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16), gs)
    assert got.dtype == jnp.bfloat16
    want = _loop(
        lhs.astype(jnp.bfloat16).astype(jnp.float32),
        rhs.astype(jnp.bfloat16).astype(jnp.float32), [40, 24],
    )
    # One rounding of a float32 sum to bf16: 2^-8 of the value.
    np.testing.assert_allclose(
        got.astype(jnp.float32), want, atol=2 ** -7 * float(jnp.max(jnp.abs(want)))
    )
