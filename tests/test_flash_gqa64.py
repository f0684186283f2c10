"""The first grouped-query flash call at head size 64 (Granite 4.0-H's
attention layers: 32 query heads, 8 key-value heads, softmax scale
1/64): the key-value head repeat in front of the kernel and
``ops/flash_attention._kept``'s model-layout branch (``o`` kept as
``[B, T, H*D]`` below 128 lanes) against XLA attention, forward and
gradients, with and without ``remat="full"``."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accelerate import remat
from dlrover_tpu.models import gpt, llama
from dlrover_tpu.ops.flash_attention import flash_attention
from tests.test_flash_attention import (
    _dense_lse,
    _loss_through_o_and_lse,
    flash_module,
)

B, T, H, HKV, D = 2, 256, 32, 8, 64
SCALE = 1.0 / 64


@pytest.fixture(scope="module")
def case():
    cfg = llama.LlamaConfig(
        vocab_size=64, block_size=T, n_layer=1, n_head=H, n_kv_head=HKV,
        n_embd=H * D, intermediate=64, dtype=jnp.float32,
    )
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    e, kv = H * D, HKV * D
    lp = {
        "wq": jax.random.normal(ks[0], (e, e)) * e ** -0.5 * 4.0,
        "wk": jax.random.normal(ks[1], (e, kv)) * e ** -0.5 * 4.0,
        "wv": jax.random.normal(ks[2], (e, kv)) * e ** -0.5,
        "wo": jax.random.normal(ks[3], (e, e)) * e ** -0.5,
    }
    h = jax.random.normal(ks[4], (B, T, e))
    w = jax.random.normal(ks[5], (B, T, e))
    return cfg, lp, h, w


def _loss(attn_fn, cfg, w, policy, h, lp):
    def half(h, lp):
        return llama.attention_half(h, lp, cfg, attn_fn, None, None)

    if policy:
        half = jax.checkpoint(half, policy=remat.full_policy())
    return jnp.sum(half(h, lp) * w)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("policy", [False, True])
def test_grouped_query_flash_at_head_size_64_agrees_with_xla(case, policy):
    cfg, lp, h, w = case
    flash = functools.partial(
        flash_attention, causal=True, scale=SCALE, interpret=True
    )
    xla = functools.partial(gpt._default_attention, causal=True, scale=SCALE)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            functools.partial(_loss, flash, cfg, w, policy), argnums=(0, 1)
        ))(h, lp)
        want, g_want = jax.jit(jax.value_and_grad(
            functools.partial(_loss, xla, cfg, w, False), argnums=(0, 1)
        ))(h, lp)
    # Float32 on both sides, the softmax in blocks against whole rows:
    # read here 2e-6 on the gradients.
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.max(jnp.abs(b))) > 0
        assert _rel(a, b) < 2e-5


def test_the_scale_is_the_configurations_not_the_head_sizes(case):
    """1/64 is not 1/sqrt(64): with the default scale the scores are
    eight times as large and the output differs."""
    _, _, h, _ = case
    q = h[..., : 4 * D].reshape(B, T, 4, D)
    with jax.default_matmul_precision("highest"):
        scaled = flash_attention(q, q, q, scale=SCALE, interpret=True)
        default = flash_attention(q, q, q, interpret=True)
        xla = gpt._default_attention(q, q, q, scale=SCALE)
    assert _rel(scaled, xla) < 1e-5
    assert _rel(default, xla) > 1e-2


def test_kept_o_is_in_the_models_layout_and_compact_kv_gets_its_gradient(case):
    """Under "full" the residuals of the block are ``o`` as
    ``[B, T, H*D]`` (not the lane-padded ``[B, H, T, 64]``) and the
    compact ``lse``; the repeat's transpose sums each group of four
    query heads back onto its key-value head."""
    cfg, lp, h, w = case
    flash = functools.partial(
        flash_attention, causal=True, scale=SCALE, interpret=True
    )
    jaxpr = jax.make_jaxpr(jax.grad(
        functools.partial(_loss, flash, cfg, w, True), argnums=(0, 1)
    ))(h, lp)

    def names(jp, acc):
        for eqn in jp.eqns:
            if eqn.primitive.name == "name":
                acc.add((eqn.params["name"], eqn.outvars[0].aval.shape))
            for v in eqn.params.values():
                for x in v if isinstance(v, (tuple, list)) else [v]:
                    x = getattr(x, "jaxpr", x)
                    if hasattr(x, "eqns"):
                        names(x, acc)
        return acc

    found = names(jaxpr.jaxpr, set())
    assert ("flash_o", (B, T, H * D)) in found
    assert ("flash_lse", (B, H, 1, T)) in found
    assert ("attn_in", (B, T, HKV * D)) in found  # k, v kept compact
    assert not [s for n, s in found if n == "flash_o" and len(s) == 4]


@pytest.mark.parametrize("split", [1, 2, 4])
def test_lse_and_its_cotangent_at_head_size_64_with_grouped_queries(
    case, split, monkeypatch
):
    """``return_lse=True`` at this call's shape (32 query heads over 8
    key-value heads, scale 1/64): lse against plain attention's, and
    dq, dk, dv with a cotangent on lse, which the backward kernel
    takes folded into its ``delta`` row. The sequence is one block
    that the diagonal crosses: run whole (``split`` 1) and as the live
    ones of its 2 x 2 and 4 x 4 sub-tiles."""
    monkeypatch.setattr(flash_module, "_BWD_SPLIT", split)
    _, _, h, _ = case
    q = h[..., : H * D].reshape(B, T, H, D)
    kv = h[..., : HKV * D].reshape(B, T, HKV, D)
    repeat = functools.partial(jnp.repeat, repeats=H // HKV, axis=2)

    def plain(q, kv):
        k = repeat(kv)
        return (
            gpt._default_attention(q, k, k, scale=SCALE),
            _dense_lse(q, k, True, scale=SCALE),
        )

    def flash(q, kv):
        k = repeat(kv)
        return flash_attention(
            q, k, k, scale=SCALE, interpret=True, return_lse=True
        )

    loss = _loss_through_o_and_lse
    with jax.default_matmul_precision("highest"):
        lse = flash(q, kv)[1]
        want_lse = plain(q, kv)[1]
        got = jax.jit(jax.grad(loss(flash), argnums=(0, 1)))(q, kv)
        want = jax.jit(jax.grad(loss(plain), argnums=(0, 1)))(q, kv)
    assert lse.shape == (B, H, T)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    for a, b in zip(got, want):
        assert _rel(a, b) < 2e-5
