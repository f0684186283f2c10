"""The held expert path's rows summed back by token (ops/rows_sum.py,
kernel ``moe_rows_sum``), interpreted on the CPU: against a plain sum
by token in float64, over the loads and the shapes the three held
cells have in small, and ``jax.grad`` through ``moe._held_experts``
against a one-hot layer.

Tiles of 16 tokens and chunks of 16 rows stand in for the chip's 256
and 128 (``TOKEN_TILE``, ``CHUNK``), so that 64 tokens are four tiles
and a buffer several chunks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.ops import rows_sum

N, D = 64, 128


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(rows_sum, "TOKEN_TILE", 16)
    monkeypatch.setattr(rows_sum, "CHUNK", 16)


def _choices(load, n_experts, held, top_k, per_token):
    """experts [N, top_k]: the router's choices under a load."""
    key = jax.random.PRNGKey(n_experts + 7 * top_k + held)
    even = jax.lax.top_k(jax.random.normal(key, (N, n_experts)), top_k)[1]
    if load == "even":
        return even
    if load == "collapsed":
        # Every token the same way, ``per_token`` of its choices held:
        # ``per_token`` groups hold every row of the buffer.
        one = jnp.concatenate([
            jnp.arange(per_token), jnp.arange(held, held + top_k - per_token)
        ])
        return jnp.broadcast_to(one, (N, top_k))
    assert load == "all_and_none"
    # Token 0 with every choice held, token 1 with none.
    return even.at[0].set(jnp.arange(top_k)).at[1].set(
        jnp.arange(held, held + top_k)
    )


def _block(experts, held, cap, j, dtype=jnp.bfloat16):
    """Block ``j``'s plan, rows and weights, and what they sum to by
    token (float64, from the values as they are held)."""
    n, k = experts.shape
    local = jnp.where(experts < held, experts, held).astype(jnp.int32)
    whole = moe._held_order(local, held)
    plan = moe._block_plan(whole, j, n, k, cap)
    rows = jax.random.normal(jax.random.PRNGKey(1), (cap, D)).astype(dtype)
    weights = jax.random.uniform(
        jax.random.PRNGKey(2), (n, k), jnp.float32, 0.05, 1.0
    )
    row_weight = moe._row_weights(weights, plan)
    live = np.asarray(plan["live"])
    token = np.asarray(plan["token"])[live]
    values = np.asarray(rows.astype(jnp.float32), np.float64)[live]
    want = np.zeros((n, D)), np.zeros((n, D))
    np.add.at(want[0], token, values * np.asarray(row_weight, np.float64)[live, None])
    np.add.at(want[1], token, values)
    return plan, rows, row_weight, want, int(live.sum())


def _close(got, want, rel=1e-6):
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got, np.float64), want, rtol=0,
        atol=rel * float(np.max(np.abs(want)) + 1e-30),
    )


@pytest.mark.parametrize("load", ["even", "collapsed", "all_and_none"])
@pytest.mark.parametrize(
    "n_experts,held,top_k,per_token",
    [(256, 8, 8, 1), (64, 8, 6, 2), (64, 16, 8, 4), (128, 16, 6, 1)],
    ids=["kimi_s", "deepseek_s", "mellum_s", "six_of_sixteen"],
)
def test_the_kernel_is_the_sum_by_token(load, n_experts, held, top_k, per_token):
    """bf16 rows, float32 weights: each token's rows times their
    weights, to 1e-6 of the largest sum (a weight rounded to bf16 would
    be 4e-3 off), and the unweighted form the backward of the rows'
    gather takes. The buffer is ``per_token`` rows a token (``cap / n``
    1, 2 and 4), with dead rows past the count under the even load and
    none under the collapsed one."""
    cap = N * per_token
    experts = _choices(load, n_experts, held, top_k, per_token)
    plan, rows, row_weight, want, live = _block(experts, held, cap, 0)
    if load == "collapsed":
        sizes = np.asarray(plan["group_sizes"])
        assert live == cap and sorted(sizes)[-per_token:] == [N] * per_token
    elif load == "even":
        assert 0 < live < cap
    else:
        in_first_block = np.asarray(plan["pair_here"]).reshape(N, top_k).sum(1)
        assert in_first_block[1] == 0 and in_first_block[0] > 0
    got = moe._tokens_of_rows(rows, row_weight, plan, N)
    _close(got, want[0])
    assert float(jnp.max(jnp.abs(got))) > 0.0
    _close(moe._tokens_of_rows(rows, None, plan, N), want[1])
    # What the layout says the kernel walks is what it is handed.
    sizes = rows_sum.layout(N, cap, held)
    assert sizes == {
        "tile": 16, "visits": cap // 16 + (N // 16) * held
    }
    assert all(v.shape == (sizes["visits"],) for v in plan["visits"])


def test_a_token_with_none_reads_zero_and_float32_rows_stay_float32():
    """A token none of whose choices is held sums to exactly zero, and
    float32 rows (the tests' toy models) are selected as they are."""
    experts = _choices("all_and_none", 32, 8, 6, 2)
    plan, rows, row_weight, want, _ = _block(experts, 8, 128, 0, jnp.float32)
    got = moe._tokens_of_rows(rows, row_weight, plan, N)
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0
    _close(got, want[0])
    _close(moe._tokens_of_rows(rows, None, plan, N), want[1], rel=1e-7)


@pytest.mark.parametrize("j", [0, 1, 2])
def test_a_block_past_the_buffer_sums_its_own_rows(j):
    """A layer past its buffer: every token sends four choices to the
    held experts and the buffer has rows for two, so the sorted rows
    are two whole blocks; each sums its own rows alone, and a third
    (which the layer would skip) sums nothing."""
    experts = _choices("collapsed", 32, 8, 6, 4)
    plan, rows, row_weight, want, live = _block(experts, 8, 2 * N, j)
    assert live == (2 * N if j < 2 else 0)
    got = moe._tokens_of_rows(rows, row_weight, plan, N)
    _close(got, want[0])
    assert (float(jnp.max(jnp.abs(got))) > 0.0) == (j < 2)


def test_its_backward_is_the_gather_and_the_two_products():
    """``jax.grad`` through the rule against autodiff of the plain
    form: the rows' gradient (weight x g by each row's token, in the
    rows' dtype), the weights' (sum of g x row) and nothing for a dead
    row."""
    experts = _choices("even", 32, 8, 6, 2)
    plan, rows, row_weight, _, live = _block(experts, 8, 128, 0)
    g = jax.random.normal(jax.random.PRNGKey(5), (N, D))

    def plain(rows, weight):
        each = rows.astype(jnp.float32) * weight[:, None]
        each = jnp.where(plan["live"][:, None], each, 0.0)
        return jax.ops.segment_sum(each, plan["token"], num_segments=N)

    got = jax.grad(
        lambda r, w: jnp.sum(moe._tokens_of_rows(r, w, plan, N) * g), (0, 1)
    )(rows, row_weight)
    want = jax.grad(lambda r, w: jnp.sum(plain(r, w) * g), (0, 1))(
        rows, row_weight
    )
    assert got[0].dtype == rows.dtype and got[1].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got[0].astype(jnp.float32)),
        np.asarray(want[0].astype(jnp.float32)),
    )
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[0][live:].astype(jnp.float32)))) == 0.0
    # The unweighted form is the transpose of the rows' gather.
    flat = jax.random.normal(jax.random.PRNGKey(6), (N, D)).astype(jnp.bfloat16)
    d_flat = jax.grad(
        lambda f: jnp.sum(moe._rows_of_tokens(f, plan).astype(jnp.float32) ** 2)
    )(flat)
    each = 2.0 * moe._rows_of_tokens(flat, plan).astype(jnp.float32)
    by_token = jax.ops.segment_sum(
        each.astype(jnp.bfloat16).astype(jnp.float32), plan["token"],
        num_segments=N,
    )
    assert d_flat.dtype == flat.dtype
    np.testing.assert_allclose(
        d_flat.astype(jnp.float32), by_token, rtol=1e-2, atol=1e-2
    )


@pytest.mark.parametrize("cap", [64, 192], ids=["three_blocks", "one_block"])
def test_the_held_experts_are_a_one_hot_layer_forward_and_backward(cap):
    """``_held_experts`` (the blocks, the grouped products, the rows'
    sum) against a layer that runs every held expert on every token
    and weighs by the 0/1 choices: the output and ``jax.grad`` in the
    tokens, the weights and the three matrices."""
    held, k, hidden = 8, 4, 32
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    flat = jax.random.normal(keys[0], (N, D))
    experts = jax.lax.top_k(jax.random.normal(keys[1], (N, 16)), k)[1]
    local = jnp.where(experts < held, experts, held).astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (N, k), jnp.float32, 0.1, 1.0)
    wi, wg = (0.1 * jax.random.normal(key, (held, D, hidden)) for key in keys[3:5])
    wo = 0.1 * jax.random.normal(keys[5], (held, hidden, D))
    w = jax.random.normal(jax.random.PRNGKey(10), (N, D))
    assert int(jnp.sum(local < held)) > cap or cap == 192

    def got(flat, weights, wi, wo, wg):
        return moe._held_experts(flat, local, weights, wi, wo, wg, held=held, cap=cap)

    def want(flat, weights, wi, wo, wg):
        share = jnp.einsum(
            "nk,nke->ne", weights, jax.nn.one_hot(local, held + 1)[..., :held]
        )
        hi = jax.lax.Precision.HIGHEST
        h = jax.nn.silu(jnp.einsum("nd,edh->neh", flat, wg, precision=hi))
        h = h * jnp.einsum("nd,edh->neh", flat, wi, precision=hi)
        return jnp.einsum("neh,ehd,ne->nd", h, wo, share, precision=hi)

    operands = (flat, weights, wi, wo, wg)
    np.testing.assert_allclose(got(*operands), want(*operands), rtol=1e-4, atol=1e-4)
    argnums = tuple(range(5))
    grads = jax.grad(lambda *a: jnp.sum(got(*a) * w), argnums)(*operands)
    ref = jax.grad(lambda *a: jnp.sum(want(*a) * w), argnums)(*operands)
    for name, a, b in zip(("flat", "weights", "wi", "wo", "wg"), grads, ref):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-3 * float(jnp.max(jnp.abs(b))), err_msg=name
        )


def test_a_cotangent_gathered_in_the_callers_dtype_is_the_same_gradient():
    """``_held_experts(dtype=bf16)`` casts its float32 sums itself, as
    ``moe_mlp`` does right after them, and the combine's backward then
    gathers the cotangent as bf16 rows, half the bytes of the float32
    ``[cap, D]`` buffer: the output and every gradient are those of
    the float32 layer cast by its caller, bit for bit."""
    import re

    held, k, hidden, cap = 8, 4, 32, 64
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    bf16 = lambda x: x.astype(jnp.bfloat16)
    flat = bf16(jax.random.normal(keys[0], (N, D)))
    experts = jax.lax.top_k(jax.random.normal(keys[1], (N, 16)), k)[1]
    local = jnp.where(experts < held, experts, held).astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (N, k), jnp.float32, 0.1, 1.0)
    wi, wg = (
        bf16(0.1 * jax.random.normal(key, (held, D, hidden)))
        for key in keys[3:5]
    )
    wo = bf16(0.1 * jax.random.normal(keys[5], (held, hidden, D)))
    w = jax.random.normal(jax.random.PRNGKey(12), (N, D))

    def layer(dtype):
        def loss(flat, weights, wi, wo, wg):
            y = moe._held_experts(
                flat, local, weights, wi, wo, wg, held=held, cap=cap,
                dtype=dtype,
            )
            assert y.dtype == (dtype or jnp.float32)
            return jnp.sum(bf16(y).astype(jnp.float32) * w)

        return jax.value_and_grad(loss, argnums=tuple(range(5)))

    operands = (flat, weights, wi, wo, wg)
    narrow, wide = layer(jnp.bfloat16), layer(None)
    for a, b in zip(jax.tree.leaves(narrow(*operands)),
                    jax.tree.leaves(wide(*operands))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
        )
    rows = lambda f: len(re.findall(
        rf"bf16\[{cap},{D}\] = gather", str(jax.make_jaxpr(f)(*operands))
    ))
    assert rows(narrow) > rows(wide)
